from .mesh import make_production_mesh

__all__ = ["make_production_mesh"]

"""The program names its device work by layer: every op of the fused ALS
sweep carries, in its ``op_name``, the scope of the layer it belongs to
(``mttkrp/mode{n}`` with ``gather`` and ``kernel`` inside it, from order 4
on ``khatri_rao`` inside ``gather``, and ``epilogue/mode{n}``), which is
what a device trace is reduced by.  The scopes are metadata alone: without
them the compiled program is the same.
"""
import contextlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import exact_lowrank_tensor
from repro.core import cpals
from repro.core.gram import gram
from repro.plan import plan_decomposition

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from chipbench import scopes  # noqa: E402

KEY = jax.random.PRNGKey(3)
RANK = 4
DIMS = {3: (24, 20, 16), 4: (10, 9, 12, 6)}


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Compile every sweep anew.  A test that runs the CLI in this process
    leaves JAX's persistent cache on, and its key leaves out op metadata:
    the unscoped sweep would be handed the scoped one's compiled text."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_sweep(impl: str, order: int = 3) -> str:
    """The optimized text of one fused sweep at a small CSF workspace."""
    t = exact_lowrank_tensor(DIMS[order], RANK, KEY)
    plan = plan_decomposition(t, impl, rank=RANK, with_stats=False)
    ws = cpals.build_workspace(t, plan)
    factors = cpals.init_factors(t.dims, RANK, KEY)
    jax.clear_caches()  # trace anew: the kernels' own jits cache their jaxprs
    return cpals._iteration_jit(False).lower(
        ws, factors, tuple(gram(a) for a in factors), jnp.float32(1.0),
        impls=plan.impls, norm_kind="2", with_fit=True).compile().as_text()


def _without_metadata(text: str) -> str:
    """``text`` less its op metadata and the source tables it indexes."""
    out, tables = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            tables = True
        elif tables and re.match(r"^(%|ENTRY|HloModule)", line):
            tables = False
        if not tables:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("impl", ["pallas", "segment"])
def test_every_layer_of_the_sweep_is_named(impl):
    names = set(re.findall(r'op_name="([^"]*)"', _compiled_sweep(impl)))
    for n in range(3):
        for scope in (rf"/mttkrp/mode{n}/(.+/)?gather/",
                      rf"/mttkrp/mode{n}/(.+/)?kernel/",
                      rf"/epilogue/mode{n}/"):
            assert any(re.search(scope, name) for name in names), scope


@pytest.mark.parametrize("impl", ["pallas", "segment"])
def test_scopes_change_nothing_but_metadata(impl, monkeypatch):
    scoped = _compiled_sweep(impl)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_sweep(impl)
    assert "/mttkrp/mode0/" in scoped and "/mttkrp/mode0/" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


@pytest.mark.parametrize("impl", ["pallas", "segment", "gather_scatter",
                                  "linearized", "linearized_pallas"])
def test_order4_chain_product_is_named_inside_gather(impl):
    """From order 4 on, the rows of the factors past the first two and
    their product carry ``gather/khatri_rao``; a trace reads them as the
    mode's gather."""
    names = set(re.findall(r'op_name="([^"]*)"',
                           _compiled_sweep(impl, order=4)))
    for n in range(4):
        chain = [name for name in names if re.search(
            rf"/mttkrp/mode{n}/(.+/)?gather/khatri_rao/", name)]
        assert chain, n
        assert {scopes.scope_of(name) for name in chain} == {
            f"mttkrp/mode{n}/gather"}


@pytest.mark.parametrize("impl", ["pallas", "segment"])
def test_order3_has_no_chain_product(impl):
    assert "khatri_rao" not in _compiled_sweep(impl)

"""The program names its device work by layer: every op of the fused ALS
sweep carries, in its ``op_name``, the scope of the layer it belongs to
(``mttkrp/mode{n}`` with ``gather`` and ``kernel`` inside it, and
``epilogue/mode{n}``), which is what a device trace is reduced by.  The
scopes are metadata alone: without them the compiled program is the same.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import exact_lowrank_tensor
from repro.core import cpals
from repro.core.gram import gram
from repro.plan import plan_decomposition

KEY = jax.random.PRNGKey(3)
RANK = 4


def _compiled_sweep(impl: str) -> str:
    """The optimized text of one fused sweep at a small CSF workspace."""
    t = exact_lowrank_tensor((24, 20, 16), RANK, KEY)
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

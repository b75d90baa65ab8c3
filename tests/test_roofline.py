"""Roofline machinery: HLO collective parsing, wire-byte model, sharding
rules, and the flash-attention path (vs the exact sdpa reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils import roofline as RL


HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[16,1024]{1,0} parameter(0)
  %all-reduce.32 = f32[16,1024,1024]{2,1,0} all-reduce(%x), channel_id=1, replica_groups=[16,16]<=[256], use_global_device_ids=true, to_apply=%add
  %ag = bf16[2048,512]{1,0} all-gather(%y), channel_id=2, replica_groups=[32,8]<=[256], dimensions={0}
  %rs = f32[128,64]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[16,16]<=[256], to_apply=%add
  %a2a = bf16[64,64]{1,0} all-to-all(%w), channel_id=4, replica_groups=[16,16]<=[256]
  %cp = f32[256]{0} collective-permute(%v), channel_id=5, source_target_pairs={{0,1}}
  %ars = (f32[128]{0}, f32[256]{0}) all-reduce-start(%a, %b), channel_id=6, replica_groups=[2,128]<=[256], to_apply=%add
  %ard = (f32[128]{0}, f32[256]{0}) all-reduce-done(%ars)
  %fus = f32[16,1024]{1,0} fusion(%p0), kind=kLoop
}
"""


def test_parse_collectives_kinds_and_groups():
    colls = RL.parse_collectives(HLO_SAMPLE)
    kinds = sorted(c["kind"] for c in colls)
    assert kinds == ["all-gather", "all-reduce", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]
    by_kind = {c["kind"]: c for c in colls if c["kind"] != "all-reduce"}
    # all-gather: 2048*512*2 bytes result, group 8
    ag = by_kind["all-gather"]
    assert ag["bytes"] == 2048 * 512 * 2 and ag["group"] == 8
    assert ag["wire"] == pytest.approx(ag["bytes"] * 7 / 8)
    # reduce-scatter: result bytes * (g-1)
    rs = by_kind["reduce-scatter"]
    assert rs["wire"] == pytest.approx(128 * 64 * 4 * 15)
    # collective-permute: result bytes
    assert by_kind["collective-permute"]["wire"] == 256 * 4


def test_parse_async_start_not_done():
    colls = [c for c in RL.parse_collectives(HLO_SAMPLE)
             if c["kind"] == "all-reduce"]
    # one sync all-reduce + one -start (the -done is skipped)
    assert len(colls) == 2
    tup = [c for c in colls if c["group"] == 128][0]
    assert tup["bytes"] == (128 + 256) * 4


def test_allreduce_wire_model():
    colls = RL.parse_collectives(HLO_SAMPLE)
    ar = [c for c in colls if c["kind"] == "all-reduce" and c["group"] == 16][0]
    b = 16 * 1024 * 1024 * 4
    assert ar["wire"] == pytest.approx(2 * b * 15 / 16)


def test_analyze_dominant_term():
    r = RL.analyze_values(flops=197e12, bytes_accessed=819e9 * 2,
                          wire_bytes=0, collectives={}, n_chips=4,
                          model_flops=197e12 * 2, kind="TPU v5 lite")
    assert r.dominant == "memory"
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.useful_ratio == pytest.approx(0.5)


def test_peaks_are_keyed_by_device_kind():
    v5e = RL.peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in v5e.source
    # a kind without published peaks is an error, never a default
    with pytest.raises(ValueError, match="no published peaks"):
        RL.peaks_for("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        RL.analyze({"flops": 1.0}, "", n_chips=1, model_flops=1.0,
                   kind="TPU v4")


def test_model_flops_estimate_kinds():
    from repro import configs
    from repro.models.config import SHAPES
    cfg = configs.get("llama3.2-3b")
    tr = RL.model_flops_estimate(cfg, SHAPES["train_4k"])
    pf = RL.model_flops_estimate(cfg, SHAPES["prefill_32k"])
    de = RL.model_flops_estimate(cfg, SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * cfg.param_count() * 256 * 4096)
    assert pf == pytest.approx(2 * cfg.param_count() * 32 * 32768)
    assert de == pytest.approx(2 * cfg.param_count() * 128)
    # MoE: active params, not total
    kimi = configs.get("kimi-k2-1t-a32b")
    assert (RL.model_flops_estimate(kimi, SHAPES["train_4k"])
            < 6 * kimi.param_count() * 256 * 4096 * 0.1)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_spec_for_divisibility_guard():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import spec_for, rules_for

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    rules = rules_for(None)
    # divisible vocab shards; non-divisible kv_heads stays replicated
    assert spec_for(("vocab", "embed"), (256000, 3072), FakeMesh(), rules) \
        == P("model")
    assert spec_for(("embed", "kv_heads", "head_dim"), (4096, 8, 128),
                    FakeMesh(), rules) == P()
    assert spec_for(("embed", "heads", "head_dim"), (4096, 64, 128),
                    FakeMesh(), rules) == P(None, "model")


def test_spec_for_no_double_axis_use():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import spec_for

    class FakeMesh:
        shape = {"data": 4, "model": 4}

    rules = {"a": "model", "b": "model"}
    # second dim wanting 'model' must stay unsharded (axis already used)
    assert spec_for(("a", "b"), (16, 16), FakeMesh(), rules) == P("model")


# ---------------------------------------------------------------------------
# flash attention (exactness vs sdpa)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["causal", "bidir", "local"])
@pytest.mark.parametrize("skip", [False, True])
def test_flash_matches_sdpa(kind, skip):
    from repro.models import layers as L
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab=64, window=48, param_dtype="float32",
                      compute_dtype="float32")
    key = jax.random.PRNGKey(0)
    B, S = 2, 128
    q = jax.random.normal(key, (B, S, 4, 16)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 2, 16)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, 2, 16))
    mask = L._train_mask(kind, S, cfg.window)[None, None, None]
    want = L._sdpa(cfg, q, k, v, mask)
    got = L._flash_attention(cfg, q, k, v, kind, qb=32, kb=32,
                             block_skip=skip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_chunked_loss_matches_full():
    import dataclasses
    from repro import configs
    from repro.models import Model
    cfg = configs.smoke_of(configs.get("llama3.2-3b"))
    m_full = Model(cfg)
    m_chunk = Model(dataclasses.replace(cfg, chunked_loss=8))
    params = m_full.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}
    l1, _ = m_full.loss(params, batch)
    l2, _ = m_chunk.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)

"""Roofline machinery: HLO collective parsing, the wire-byte model and the
per-device-kind peaks."""
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

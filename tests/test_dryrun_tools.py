"""The dry-run's HLO collective-bytes parser and roofline arithmetic."""
import pytest

from repro.launch import dryrun

HLO = """
ENTRY %main {
  %p0 = f32[256,1024]{1,0} parameter(0)
  %ag = f32[256,16384]{1,0} all-gather(%p0), dimensions={1}
  %ar = bf16[1024,1024]{1,0} all-reduce(%x), to_apply=%add
  %rs = f32[16,1024]{1,0} reduce-scatter(%y), dimensions={0}
  %cp = f32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%q, %r)
  %fusion.1 = f32[2]{0} fusion(%ag), kind=kLoop, calls=%fused_all_gather
}
"""


def test_collective_bytes_parses_all_kinds():
    out = dryrun.collective_bytes(HLO)
    b = out["bytes"]
    assert b["all-gather"] == 256 * 16384 * 4
    assert b["all-reduce"] == 1024 * 1024 * 2
    assert b["reduce-scatter"] == 16 * 1024 * 4
    assert b["collective-permute"] == 8 * 4
    assert b["all-to-all"] == 2 * 16 * 4
    assert out["counts"]["all-gather"] == 1
    assert out["total_bytes"] == sum(b.values())


def test_collective_bytes_ignores_fusion_names():
    out = dryrun.collective_bytes(
        "%f = f32[1024]{0} fusion(%a), calls=%fused_all_reduce_stuff")
    assert out["total_bytes"] == 0


def test_type_bytes_dtypes():
    assert dryrun._type_bytes("bf16[2,3]") == 12
    assert dryrun._type_bytes("f32[10]") == 40
    assert dryrun._type_bytes("pred[8]") == 8
    assert dryrun._type_bytes("s8[5] u32[2]") == 13


def test_roofline_terms():
    from benchmarks import roofline
    terms = roofline.terms(flops=1e15, bytes_accessed=1e12,
                           collective_bytes=1e9, n_devices=256,
                           device_kind="TPU v5 lite")
    peaks = roofline.peaks("TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(
        1e15 / (256 * peaks["flops"]), rel=1e-6)
    assert terms["memory_s"] == pytest.approx(
        1e12 / (256 * peaks["hbm_bytes_per_s"]), rel=1e-6)
    assert terms["collective_s"] == pytest.approx(
        1e9 / (256 * peaks["ici_bytes_per_s"]), rel=1e-6)
    assert terms["bottleneck"] in ("compute", "memory", "collective")


def test_roofline_unknown_device_kind_raises():
    from benchmarks import roofline
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.terms(flops=1.0, bytes_accessed=1.0, collective_bytes=0.0,
                       n_devices=1, device_kind="cpu")

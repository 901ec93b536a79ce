"""HLO text parser: shapes, group sizes, operand-byte conventions."""
from repro.analysis.hlo import analyze_collectives, shape_bytes


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[16]") == 32
    assert shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert shape_bytes("pred[8]") == 8
    assert shape_bytes("f32[]") == 4


HLO = """
HloModule test
ENTRY %main {
  %ar = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  %ag = f32[4096]{0} all-gather(%y), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[256]{0} reduce-scatter(%z), channel_id=3, replica_groups=[2,4]<=[8], to_apply=%add
  %aa = bf16[512]{0} all-to-all(%w), channel_id=4, replica_groups=[1,8]<=[8]
  %cp = f32[100]{0} collective-permute(%v), channel_id=5, source_target_pairs={{0,1}}
}
"""


def test_collective_operand_conventions():
    res = analyze_collectives(HLO)
    by = res["bytes_by_type"]
    assert by["all-reduce"] == 1024 * 4                    # operand == output
    assert by["all-gather"] == 4096 * 4 / 4                # output / group
    assert by["reduce-scatter"] == 256 * 4 * 4             # output * group
    assert by["all-to-all"] == 512 * 2
    assert by["collective-permute"] == 100 * 4
    assert res["count_by_type"]["all-reduce"] == 1
    assert res["num_while"] == 0
    assert len(res["top_collectives"]) == 5


def test_async_pairs_counted_once():
    hlo = """
  %s = f32[1000]{0} all-reduce-start(%x), replica_groups=[1,8]<=[8]
  %d = f32[1000]{0} all-reduce-done(%s)
"""
    res = analyze_collectives(hlo)
    assert res["count_by_type"]["all-reduce"] == 1
    assert res["bytes_by_type"]["all-reduce"] == 4000


def test_while_detected():
    hlo = "%w = (s32[], f32[4]) while(%t), condition=%c, body=%b"
    assert analyze_collectives(hlo)["num_while"] == 1


def test_roofline_peaks_keyed_by_device_kind():
    import pytest

    from repro.analysis.roofline import Roofline, peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")
    roof = Roofline(flops_per_chip=197e12, bytes_per_chip=0.0,
                    coll_bytes_per_chip=0.0, chips=1,
                    model_flops_global=197e12, device_kind="TPU v5 lite")
    assert roof.terms()["compute_s"] == 1.0
    with pytest.raises(ValueError):
        Roofline(1.0, 1.0, 0.0, 1, 1.0, device_kind="TPU v9").terms()

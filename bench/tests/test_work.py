"""Operation and byte counts at deepseek-67b shapes, against numbers worked
by hand."""
import json

import pytest

import peaks
import run
from conftest import BENCH


@pytest.fixture(scope="module")
def conf():
    return json.loads((BENCH / "configs" / "deepseek-67b-analog.json")
                      .read_text())


@pytest.fixture(scope="module")
def ref(conf):
    return run.load_module(BENCH / "models" / f"{conf['reference']}.py")


def test_params(conf, ref):
    # q, o: 8192 x 8192; k, v: 8192 x 1024; gate, up, down: 8192 x 22016
    assert ref.layer_params(conf) == 2 * 8192 * 8192 + 2 * 8192 * 1024 \
        + 3 * 8192 * 22016 == 692_060_160
    assert ref.head_params(conf) == 8192 * 25600 == 209_715_200


def test_decode_row(conf, ref):
    # a decode row at position 999 attends positions 0..999
    f, b = ref.attention_call(conf, [(999, 1, True)])
    assert f == 4 * 64 * 128 * 1000 == 32_768_000
    # K and V of 1000 positions, 8 heads x 128, float32; the new K, V row;
    # one query of 64 heads and its output
    assert b == 2 * 1000 * 8 * 128 * 4 + 2 * 8 * 128 * 4 + 2 * 64 * 128 * 4
    assert b == 8_265_728


def test_prefill_chunk(conf, ref):
    # 32 prompt positions 64..95: pairs = 32 * 64 + 32 * 33 / 2 = 2576
    f, b = ref.attention_call(conf, [(64, 32, False)])
    assert f == 4 * 64 * 128 * 2576
    assert b == 2 * 96 * 8 * 128 * 4 + 2 * 32 * 8 * 128 * 4 \
        + 2 * 32 * 64 * 128 * 4


def test_model_flops(conf, ref):
    # 2 layers of 692,060,160, the head, attention over 1000 positions
    got = ref.model_flops(conf, [(999, 1, True)])
    assert got == 2 * 692_060_160 * 2 + 2 * 209_715_200 \
        + 4 * 64 * 128 * 1000 * 2 == 3_253_207_040
    # a prefill row that does not sample skips the head
    assert ref.model_flops(conf, [(0, 32, False)]) == \
        2 * 692_060_160 * 2 * 32 + 4 * 64 * 128 * (32 * 33 // 2) * 2


def test_roofline_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bound_s(197e12, 0) == 1.0
    assert p.bound_s(0, 819e9) == 1.0

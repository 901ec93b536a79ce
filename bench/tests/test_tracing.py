"""The trace reduction on a synthetic trace with known intervals."""
import pytest

import tracing
from tracing import Event, Trace

MS = 1_000_000


def synthetic():
    # a 100 ms window; two steps of a decode program, one chunk program
    mods = [Event("jit_paged_decode_step(3)", 10 * MS, 20 * MS),
            Event("jit_chunk_step(7)", 40 * MS, 30 * MS),
            Event("jit_paged_decode_step(3)", 80 * MS, 10 * MS)]
    W = "%params__decoder____layer_000____ffn____wd____w__.1"
    ops = [Event("fusion.1", 10 * MS, 15 * MS,
                 f"%fusion.1 = f32[32,8192] fusion(f32[22016,8192] {W})"),
           Event("paged_attention_decode.2", 25 * MS, 5 * MS),
           Event("fusion.3", 40 * MS, 20 * MS),
           Event("fusion.4", 55 * MS, 15 * MS,
                 "%fusion.4 = f32[32] fusion(f32[32,8] "
                 "%paged_attention_decode.2)"),
           Event("paged_attention_decode.5", 80 * MS, 4 * MS),
           Event("fusion.1", 84 * MS, 6 * MS,
                 f"%fusion.1 = f32[32,8192] fusion(f32[22016,8192] {W})")]
    host = [Event("bench.window", 0, 100 * MS),
            Event("bench.step", 5 * MS, 32 * MS),
            Event("bench.submit", 70 * MS, 3 * MS),
            Event("bench.step", 74 * MS, 20 * MS)]
    return Trace({"/device:TPU:0": mods}, {"/device:TPU:0": ops}, host)


def test_union_and_gaps():
    evs = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5)]
    assert tracing.union_ns(evs) == 20
    assert tracing.gaps(evs, 0, 50) == [(15, 30), (35, 50)]
    assert tracing.gaps(evs, 2, 12) == []


def test_busy_window_and_idle():
    tr = synthetic()
    lo, hi = tracing.window(tr)
    assert (lo, hi) == (0, 100 * MS)
    # busy: 10-30, 40-70, 80-90 -> 60 ms of 100
    assert tracing.busy_s(tr, lo, hi) == pytest.approx(0.060)
    gaps = tracing.idle_gaps(tr, lo, hi)
    # idle: 0-10 (step 5-10 covers half: step), 30-40 (step 5-37), 70-80
    # (submit 70-73 vs step 74-80: step covers more), 90-100 (step 90-94)
    assert [g[1] for g in gaps] == pytest.approx([0.01] * 4)
    assert sorted(g[0] for g in gaps) == ["bench.step"] * 4


def test_program_calls_and_kernels():
    tr = synthetic()
    dec = tracing.calls(tr, "paged_decode_step")
    assert [e.dur_ns for e in dec] == [20 * MS, 10 * MS]
    assert len(tracing.calls(tr, "chunk_step")) == 1
    k = tracing.kernel_events(tr, "paged_attention_decode")
    assert sum(e.dur_ns for e in k) == 9 * MS
    # a consumer of the kernel's output is not the kernel
    assert len(k) == 2
    top = tracing.top_ops(tr, 0, 100 * MS, n=2)
    assert top[0][0] == "fusion.1 [decoder/layer_000/ffn/wd/w]"
    assert top[0][1] == pytest.approx(0.021)
    assert top[1] == ["fusion.3", pytest.approx(0.020)]


def test_op_name():
    assert tracing.op_name("%paged_prefill.2 = f32[32,8] custom-call("
                           "s32[32,2] %copy-done.71)") == "paged_prefill.2"


def test_clip_to_window():
    tr = synthetic()
    assert tracing.busy_s(tr, 50 * MS, 85 * MS) == pytest.approx(0.025)

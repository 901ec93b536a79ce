"""The program's spans and scopes read from a profiler trace: the two readers
on synthetic traces, the reading of a hand-encoded XSpace, and a tiny engine
traced on the CPU."""
from types import SimpleNamespace

import pytest

import engine_trace
import tracing
from engine_trace import EngineTrace, Op, Span
from test_tracing import MS, synthetic

WD = "jit(paged_decode_step)/jit(main)/decoder/layer_000/ffn/wd"
T, G = "3:python3", "4:python3"
STEPS = [("step", 5, 37), ("admit", 5, 6), ("prepare", 6, 9),
         ("tables", 7, 9), ("dispatch", 9, 10), ("wait", 10, 31),
         ("book", 31, 35), ("emit", 35, 37),
         ("step", 74, 96), ("admit", 74, 75), ("prepare", 75, 78),
         ("dispatch", 78, 80), ("wait", 80, 90), ("book", 90, 92),
         ("emit", 92, 96), ("step", 100, 105)]


def engine():
    """The engine's spans of two decode steps on the server's thread (and
    one after the window), a wait on another thread, and EMT phase scopes
    on ops of the synthetic trace's programs."""
    spans = [Span("bench.window", 0, 100 * MS, "0:python3")]
    spans += [Span(f"engine.{n}", a * MS, (b - a) * MS, T)
              for n, a, b in STEPS]
    spans += [Span("engine.wait", 81 * MS, 4 * MS, G)]
    ops = [Op(10 * MS, 15 * MS, f"{WD}/emt.weights/reduce_max:"),
           Op(40 * MS, 20 * MS, f"{WD}/emt.weights/reduce_max:"),
           Op(55 * MS, 15 * MS, f"{WD}/emt.crossbar/dot_general:"),
           Op(84 * MS, 6 * MS, f"{WD}/emt.energy/reduce_sum:")]
    return EngineTrace(sorted(spans, key=lambda s: s.start_ns), ops)


def test_step_host_ms():
    # steps starting in the window: 32 - 21 and 22 - 10 ms; the wait on
    # another thread and the step at the window's end do not count
    assert engine_trace.step_host_ms(engine(), 0, 100 * MS) == \
        pytest.approx(11.5)
    assert engine_trace.step_host_ms(engine(), 40 * MS, 70 * MS) is None


def test_scoped_ms():
    # weights 15 ms + energy 6 ms in two decode calls; the weights op of
    # the chunk call and the crossbar op do not count
    calls = tracing.calls(synthetic(), "paged_decode_step")
    prep = ("emt.weights", "emt.energy")
    assert engine_trace.scoped_ms(engine(), calls, prep) == \
        pytest.approx(10.5)
    assert engine_trace.scoped_ms(engine(), calls, ("emt.noise",)) is None
    assert engine_trace.scoped_ms(engine(), [], prep) is None


def _pb(*fields):
    """Serialize ``(field number, value)`` pairs as a protobuf message: an
    int as a varint, str or bytes as a length-delimited field."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint((f << 3) | 2) + varint(len(v)) + v
    return out


def _xspace(ops, spans):
    """An XSpace of one TPU plane holding ``ops`` (HLO text, scope, start
    ns, duration ns) on its ``XLA Ops`` line, each scope a ``tf_op`` string
    stat of the op's event metadata, and one host plane holding ``spans``
    (line name, span name, start ns, duration ns), a line per name."""
    def event(mid, a, d):
        return _pb((1, mid), (2, int(a * 1000)), (3, int(d * 1000)))

    def meta(mid, *fields):
        return _pb((1, mid), (2, _pb((1, mid), *fields)))

    tpu = [(1, 1), (2, "/device:TPU:0"),
           (3, _pb((1, 1), (2, "XLA Ops"), (3, 0),
                   *[(4, event(k + 1, a, d))
                     for k, (_, _, a, d) in enumerate(ops)])),
           (5, meta(7, (2, "tf_op")))]
    tpu += [(4, meta(k + 1, (2, text), (5, _pb((1, 7), (5, scope)))))
            for k, (text, scope, _, _) in enumerate(ops)]
    names = sorted({s[1] for s in spans})
    lines = sorted({s[0] for s in spans})
    host = [(1, 2), (2, "/host:CPU")]
    host += [(3, _pb((1, j + 1), (2, line), (3, 0),
                     *[(4, event(names.index(n) + 1, a, d))
                       for ln, n, a, d in spans if ln == line]))
             for j, line in enumerate(lines)]
    host += [(4, meta(k + 1, (2, n))) for k, n in enumerate(names)]
    return _pb((1, _pb(*tpu)), (1, _pb(*host)))


def _write(root, ops, spans):
    d = root / "analog-reason" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(ops, spans))


def test_load_reads_op_scopes_and_host_threads(tmp_path):
    # an XSpace as the TPU profiler writes it: each op's op_name metadata is
    # a "tf_op" stat of its event metadata, as a string (op 1) or as a
    # reference to a stat metadata's name (op 2); ops without it are dropped
    fused = "%fusion.1 = f32[8] fusion(f32[8,128] %p), kind=kLoop"
    dot = "%fusion.2 = f32[8,8] fusion(f32[8,128] %p), kind=kOutput"
    scope = f"{WD}/emt.weights/reduce_max:"
    tpu = _pb(
        (1, 1), (2, "/device:TPU:0"),
        (3, _pb((1, 1), (2, "XLA Ops"), (3, 1000),
                (4, _pb((1, 1), (2, 2_000_000), (3, 5_000_000))),
                (4, _pb((1, 2), (2, 9_000_000), (3, 1_000_000))),
                (4, _pb((1, 3), (2, 9_500_000), (3, 1_000_000))))),
        (4, _pb((1, 1), (2, _pb((1, 1), (2, fused), (4, "fusion.1"),
                                (5, _pb((1, 7), (5, scope))))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, dot), (4, "fusion.2"),
                                (5, _pb((1, 7), (7, 8))))))),
        (4, _pb((1, 3), (2, _pb((1, 3), (2, "%copy.3 = f32[8] copy()"))))),
        (5, _pb((1, 7), (2, _pb((1, 7), (2, "tf_op"))))),
        (5, _pb((1, 8), (2, _pb((1, 8), (2, f"{WD}/emt.crossbar/dot:"))))))
    host = _pb(
        (1, 2), (2, "/host:CPU"),
        (3, _pb((1, 5), (2, "python3"), (3, 1000),
                (4, _pb((1, 1), (2, 0), (3, 9_000_000))),
                (4, _pb((1, 2), (2, 1_000_000), (3, 1_000_000))))),
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "engine.step"))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "jit_compile"))))))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_pb((1, tpu), (1, host)))
    et = engine_trace.load(str(tmp_path))
    assert [o.scope for o in et.ops] == [scope, f"{WD}/emt.crossbar/dot:"]
    assert et.ops[0].start_ns == pytest.approx(3_000)
    assert et.ops[0].dur_ns == pytest.approx(5_000)
    assert [(s.name, s.thread) for s in et.spans] == [
        ("engine.step", "0:python3")]


def read(metric, window):
    """The value of the per-layer reader ``bench/metrics/<metric>.py`` on
    the synthetic trace's programs and the given window."""
    import run

    reader = run.load_module(run.BENCH / "metrics" / f"{metric}.py")
    return reader.read(SimpleNamespace(trace=synthetic(), window=window))


@pytest.mark.parametrize("spans", [True, False], ids=["spans", "no-spans"])
def test_readers_on_the_run_trace(tmp_path, monkeypatch, spans):
    # the readers find the run's trace where bench/run.py writes it; on a
    # program without engine spans or EMT scopes both leave their metric
    # out instead of raising
    monkeypatch.setattr(engine_trace, "TRACES", tmp_path)
    et = engine()
    plain = "jit(paged_decode_step)/jit(main)/decoder/layer_000/dot_general:"
    ops = [(f"%fusion.{k} = f32[8] fusion()", o.scope if spans else plain,
            o.start_ns, o.dur_ns) for k, o in enumerate(et.ops)]
    _write(tmp_path, ops, [(s.thread, s.name, s.start_ns, s.dur_ns)
                           for s in et.spans
                           if spans or s.name == "bench.window"])
    window = (0, 100 * MS)
    if spans:
        assert read("step_host_ms", window) == pytest.approx(11.5)
        assert read("emt_weight_prep_ms", window) == pytest.approx(10.5)
    else:
        assert read("step_host_ms", window) is None
        assert read("emt_weight_prep_ms", window) is None
    # another run's trace, or none at all
    assert read("step_host_ms", (0, 99 * MS)) is None
    monkeypatch.setattr(engine_trace, "TRACES", tmp_path / "none")
    assert read("emt_weight_prep_ms", window) is None


def test_engine_step_spans_nest(tmp_path):
    # a tiny paged engine serving two requests (chunked prefill, then
    # decode) under the profiler, read back as the benchmark reads a trace
    import jax
    import numpy as np

    import run
    from repro.serve.engine import GenRequest

    eng = run.build(run.load_json(run.BENCH / "tests" / "tiny-analog.json"),
                    seed=5).engine
    for k, n in enumerate((3, 9)):
        eng.submit(GenRequest(prompt=np.arange(1, n + 1, dtype=np.int32),
                              max_new=3, seed=k))
    with jax.profiler.trace(str(tmp_path)):
        eng.drain()
    host = engine_trace.load(str(tmp_path)).spans
    steps = [e for e in host if e.name == "engine.step"]
    assert len(steps) >= 4
    for s in steps:
        inner = [e for e in host if e is not s and e.thread == s.thread
                 and s.start_ns <= e.start_ns and e.end_ns <= s.end_ns]
        assert [e.name for e in inner] == [
            "engine.admit", "engine.prepare", "engine.tables",
            "engine.dispatch", "engine.wait", "engine.book", "engine.emit"]
        prepare, tables = inner[1], inner[2]
        assert prepare.start_ns <= tables.start_ns
        assert tables.end_ns <= prepare.end_ns
        phases = [e for e in inner if e is not tables]
        assert all(a.end_ns <= b.start_ns
                   for a, b in zip(phases, phases[1:]))
    assert {e.thread for e in host} == {steps[0].thread}

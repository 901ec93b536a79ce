"""Run one cell of the benchmark once, on the chip this process is given.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``: published sizes, the cut, the serving spec)
and a traffic mix (``bench/traffic/<name>.json``).  One run:

1. refuses to run without an accelerator, or with fewer chips than the cell
   asks for;
2. set-up: builds the served engine from the configuration, makes its
   weights on the device from ``--seed`` in one jitted call, and serves
   warm-up requests whose lengths reach every block-table view bucket, so
   every step program is compiled (or read from the persistent cache in
   ``.jax_cache``) before the window; for an ``in_flight`` mix, starts the
   batch the window opens on (``bench/traffic.py``);
3. measures ``--seconds`` of the mix through ``StreamingServer``: the
   end-to-end metrics with ``--trace 0``, the per-layer metrics (one reader
   each, ``bench/metrics/<name>.py``) from a profiler trace with
   ``--trace 1``;
4. frees the served state and compares a seeded sample of the finished
   requests with the configuration's plain reference (``bench/models``),
   holding each compared number to its limit (``bench/limits/<cell>.json``).

The last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import drive  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


class RunError(RuntimeError):
    """A run that cannot produce a result: no chip, a bad cell, a fault."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, name: str) -> SimpleNamespace:
    """The workload, its configuration file and its mix, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(ROOT / confs[w["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits_path = BENCH / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return SimpleNamespace(
        name=name, chips=w["chips"], conf=conf, mix=mix, limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def require_chips(n: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        raise RunError(f"needs an accelerator; JAX found only {d.platform!r}"
                       f" ({d.device_kind}): there is no CPU fallback")
    if len(devs) < n:
        raise RunError(f"the cell needs {n} chips, JAX found {len(devs)}")
    print(f"device: {d.platform} {d.device_kind!r} x {len(devs)}",
          file=sys.stderr, flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``): only a cell's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles in this process, and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def noise_seed(seed: int) -> int:
    """The fluctuation seed the engine serves with (a uint32)."""
    return int(seed) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def build(conf: dict, seed: int):
    """The served engine of ``conf``, with seeded weights on the device."""
    from repro.models import lm
    from repro.nn.param import abstract_params
    from repro.serve.spec import ServeSpec

    spec = ServeSpec.from_dict(dict(conf["serve"], seed=noise_seed(seed)))
    cfg = spec.build_config()
    ref = load_module(BENCH / "models" / f"{conf['reference']}.py")
    check.config_matches(conf, cfg, ref)
    shapes = abstract_params(lm.specs(cfg))
    check.tree_matches(shapes, conf, ref)
    params = weights.make_tree(seed, shapes, ref.make_init(conf))
    engine = spec.build_engine(cfg, params)
    return SimpleNamespace(spec=spec, cfg=cfg, engine=engine, ref=ref)


def warm_up(engine, spec, vocab: int, seed: int) -> int:
    """Serve one request alone per block-table view bucket: its prompt ends
    in that bucket (chunk steps walk every bucket below it) and its one
    decode step reads there.  Returns the number of warm-up requests."""
    from repro.serve.engine import GenRequest

    rng = np.random.default_rng([seed, 7])
    views, b = [], spec.block_size
    while True:
        views.append(min(b, spec.max_len))
        if b >= spec.max_len:
            break
        b *= 2
    for v in views:
        n = v // 2 + 1 if v > spec.block_size else max(1, v // 2)
        n = min(n, spec.max_len - 2)
        engine.submit(GenRequest(
            prompt=rng.integers(0, vocab, n).astype(np.int32), max_new=2,
            seed=v))
        engine.drain()
    engine.reset_metrics()
    return len(views)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def measure(cell, seed: int, seconds: float, trace: bool, device: dict,
            trace_dir: str | None = None, clock: CompileClock | None = None,
            t0: float = T0):
    """Set up, warm up and measure one window.  Returns the record the
    metrics and the check are computed from; the served state is freed."""
    import jax

    from repro.serve.server import StreamingServer

    clock = clock or CompileClock()
    sut = build(cell.conf, seed)
    spec, eng = sut.spec, sut.engine
    vocab = cell.conf["vocab_size"]
    warm_up(eng, spec, vocab, seed)
    reqs = traffic.generate(cell.mix, seed, vocab, spec.batch_size)
    srv = StreamingServer(eng, max_pending=spec.max_pending)
    srv.start()
    w = drive.start_in_flight(srv, reqs, spec.batch_size, cell.mix)
    steps = drive.StepLog(eng) if trace else None
    compiles0 = clock.count
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    c0, cpu0 = eng.metrics(), time.process_time()
    setup_s = time.monotonic() - t0
    span = (jax.profiler.TraceAnnotation("bench.window") if trace
            else contextlib.nullcontext())
    with span:
        w = drive.run_window(srv, eng, reqs, cell.mix, seconds, w,
                             annotate=trace)
    c1 = eng.metrics()
    # the window's own steps: the in-flight start stepped before it opened
    counters = {"steps": c1["steps"] - c0["steps"],
                "shard_occupancy": [b - a for a, b in zip(
                    c0["shard_occupancy"], c1["shard_occupancy"])]}
    host = {"steps": counters["steps"], "cpu_s": time.process_time() - cpu0}
    srv.stop(drain=False)
    jax.block_until_ready(eng.cache)
    if trace:
        jax.profiler.stop_trace()
        steps.close()
    rec = SimpleNamespace(
        seed=seed, seconds=seconds, setup_s=setup_s,
        window_compiles=clock.count - compiles0,
        t_open=w.t_open, t_close=w.t_close,
        out_tokens=drive.tokens_in_window(w),
        attempted=len(w.handles) + w.rejected, rejected=w.rejected,
        errors=sum(1 for h in w.handles
                   if h.done and h.result(timeout=0).done_reason == "error"),
        finished=[(r.prompt, np.asarray(res.tokens), r.max_new)
                  for r, res in drive.finished(w)],
        counters=counters, host=host, batch=spec.batch_size,
        max_len=spec.max_len,
        steps=steps.steps if steps else [],
        step_times=steps.times if steps else [],
        memory_peak_bytes=memory_peak(device["count"]),
        ref=sut.ref)
    # free the served state before the reference runs
    del w, srv, steps, eng, sut
    gc.collect()
    return rec


def memory_peak(n: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def end_to_end(cell, rec) -> dict:
    values = {"setup_s": rec.setup_s,
              "out_tok_s": rec.out_tokens / rec.seconds}
    out = {}
    for m in cell.end_to_end:
        v = values.get(m["name"])
        if v is None or not np.isfinite(v):
            raise RunError(f"end-to-end metric {m['name']} has no reading")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def per_layer(cell, rec, tr, device: dict) -> dict:
    import peaks as peaks_mod

    ctx = SimpleNamespace(
        rec=rec, trace=tr, conf=cell.conf, mix=cell.mix, ref=rec.ref,
        peaks=peaks_mod.peaks_for(device["kind"]),
        window=_trace_window(tr, rec))
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _trace_window(tr, rec):
    import tracing as trace_mod

    w = trace_mod.window(tr)
    if w is None:
        raise RunError("the trace holds no bench.window span")
    return w


def run(cell, seed: int, seconds: float, trace: bool, device: dict) -> dict:
    """One whole run: the result line's object."""
    import tracing as trace_mod

    trace_dir = str(ROOT / ".bench_trace" / cell.name)
    rec = measure(cell, seed, seconds, trace, device, trace_dir)
    device = dict(device, memory_peak_bytes=rec.memory_peak_bytes)
    print(f"window: {rec.host['steps']} engine steps, host cpu "
          f"{rec.host['cpu_s']:.3f} s", file=sys.stderr, flush=True)
    checks = check.compare(cell, rec, noise_seed(seed))
    result = {"correct": check.correct(checks),
              "attempted": rec.attempted,
              "failed": rec.rejected + rec.errors,
              "metrics": {}, "device": device}
    if trace:
        tr = trace_mod.load(trace_dir)
        lo, hi = _trace_window(tr, rec)
        device["busy_s"] = trace_mod.busy_s(tr, lo, hi)
        device["window_s"] = (hi - lo) * 1e-9
        result["metrics"] = per_layer(cell, rec, tr, device)
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr, lo, hi),
            "idle_gaps": trace_mod.idle_gaps(tr, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = end_to_end(cell, rec)
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise; keep what
    # a run writes under $TMPDIR (read when the runtime starts, below)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cell = cell_of(bench, args.workload)
        device = require_chips(cell.chips)
        enable_compile_cache()
        result = run(cell, args.seed, args.seconds, bool(args.trace), device)
    except (RunError, check.ConfigMismatch) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

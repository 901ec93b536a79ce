"""Reduce a profiler trace to device busy time, step programs and kernels.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  A TPU
plane (``/device:TPU:<n>``) holds an ``XLA Modules`` line, one event per
execution of a compiled program, and an ``XLA Ops`` line, one event per
operation run inside it.  The host plane holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``), named ``bench.<what>``.

Everything here works on :class:`Event` tuples, so the reduction is tested
on synthetic traces without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence


class Event(NamedTuple):
    name: str                    # the op's HLO name ("fusion.12") or the
    #                              program's module name
    start_ns: float
    dur_ns: float
    detail: str = ""             # the op's HLO text, if recorded

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    modules: Dict[str, List[Event]]     # device plane -> program executions
    ops: Dict[str, List[Event]]         # device plane -> operations
    host: List[Event]                   # the benchmark's bench.* spans


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    modules, ops, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [
                        Event(op_name(e.name), e.start_ns, e.duration_ns,
                              e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(modules, ops, sorted(host, key=lambda e: e.start_ns))


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


_PARAM = re.compile(r"%params__([A-Za-z0-9_]+?)__\.\d+")


def label(e: Event) -> str:
    """An op's name and the first weight it reads, for the breakdown."""
    m = _PARAM.search(e.detail)
    if not m:
        return e.name
    return f"{e.name} [{m.group(1).replace('____', '/')}]"


def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if cur_e is None or e.start_ns > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start_ns, e.end_ns
        else:
            cur_e = max(cur_e, e.end_ns)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[tuple]:
    """Idle (start, end) intervals of ``[lo, hi]`` that no event covers."""
    out, t = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > t:
            out.append((t, min(e.start_ns, hi)))
        t = max(t, e.end_ns)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an operation ran, averaged over the
    device planes."""
    if not tr.ops:
        return 0.0
    tot = 0.0
    for evs in tr.ops.values():
        tot += union_ns(clip(evs, lo, hi))
    return tot / len(tr.ops) * 1e-9


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(e._replace(start_ns=s, dur_ns=t - s))
    return out


def calls(tr: Trace, program: str) -> List[Event]:
    """Executions of the step program whose module name holds ``program``
    (first device plane), in time order."""
    for evs in tr.modules.values():
        return sorted((e for e in evs if program in e.name),
                      key=lambda e: e.start_ns)
    return []


def kernel_events(tr: Trace, kernel: str) -> List[Event]:
    """Device operations named ``kernel`` or ``kernel.<n>``."""
    for evs in tr.ops.values():
        return [e for e in evs if e.name.split(".")[0] == kernel]
    return []


def window(tr: Trace) -> Optional[tuple]:
    """(first, last) nanosecond of the benchmark's ``bench.window`` span."""
    w = [e for e in tr.host if e.name == "bench.window"]
    if not w:
        return None
    return w[0].start_ns, w[-1].end_ns


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` device operations (by name) that took most time."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for e in clip(evs, lo, hi):
            key = label(e)
            tot[key] = tot.get(key, 0.0) + e.dur_ns
    k = max(1, len(tr.ops))
    return [[name, ns / k * 1e-9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` longest device-idle intervals, each named by the host span
    that covers most of it (``bench.step``, ``bench.submit``, ...), or
    ``host.other`` where no span of the benchmark covers it."""
    evs = next(iter(tr.ops.values()), [])
    out = []
    longest = sorted(gaps(clip(evs, lo, hi), lo, hi),
                     key=lambda g: g[0] - g[1])[:n]
    for a, b in longest:
        best, cover = "host.other", 0.0
        for h in tr.host:
            if h.name == "bench.window":
                continue
            c = min(b, h.end_ns) - max(a, h.start_ns)
            if c > cover:
                best, cover = h.name, c
        out.append([best, (b - a) * 1e-9])
    return out

"""The program's own spans and scopes in a profiler trace.

The serving engine writes host spans named ``engine.<phase>`` on the thread
that steps it, and ``emt_dense`` scopes each device op under the layer's tag
and ``emt.<phase>``.  :mod:`tracing` keeps the benchmark's ``bench.*`` spans
and each op's HLO text only; this module reads the rest of the same
``.xplane.pb``: the engine spans with their thread, and each device op's
``op_name`` metadata (JAX's name stack,
``jit(<program>)/.../<layer tag>/emt.<phase>/<primitive>``), which is the
``tf_op`` stat of the op's event metadata.

On a program without these spans or scopes every reading is empty, and the
metrics that read them are left out of the result line.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import pathlib
from typing import Dict, List, NamedTuple, Optional, Sequence

# where bench/run.py writes the trace of a --trace 1 run, one directory per
# cell; it is read, then deleted, before the run prints its result
TRACES = pathlib.Path(__file__).resolve().parents[1] / ".bench_trace"


class Span(NamedTuple):
    name: str                    # engine.<phase> or bench.window
    start_ns: float
    dur_ns: float
    thread: str = ""             # "<line index>:<line name>" of its plane

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Op(NamedTuple):
    start_ns: float
    dur_ns: float
    scope: str                   # the op's op_name metadata


class EngineTrace(NamedTuple):
    spans: List[Span]            # host spans, by start
    ops: List[Op]                # scoped ops of the first device plane


def load(trace_dir: str) -> EngineTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _load_file(files[-1], os.path.getmtime(files[-1]))


@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime: float) -> EngineTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        xspace = f.read()
    scopes = op_scopes(xspace)
    spans, ops = [], None
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            for line in plane.lines:
                if line.name == "XLA Ops" and ops is None:
                    ops = [Op(e.start_ns, e.duration_ns, scopes[e.name])
                           for e in line.events if e.name in scopes]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans.extend(Span(e.name, e.start_ns, e.duration_ns,
                                  f"{k}:{line.name}")
                             for e in line.events
                             if e.name.startswith("engine.")
                             or e.name == "bench.window")
    return EngineTrace(sorted(spans, key=lambda s: s.start_ns), ops or [])


def of(ctx) -> Optional[EngineTrace]:
    """The trace of the run whose metrics are being read: the newest under
    :data:`TRACES` whose ``bench.window`` span is ``ctx.window``.  None if
    there is no such trace."""
    try:
        et = load(str(TRACES))
    except FileNotFoundError:
        return None
    w = [s for s in et.spans if s.name == "bench.window"]
    if not w or (w[0].start_ns, w[-1].end_ns) != tuple(ctx.window):
        return None
    return et


def step_host_ms(et: EngineTrace, lo: float, hi: float) -> Optional[float]:
    """Mean over the ``engine.step`` spans that start in ``[lo, hi)`` of the
    step less its ``engine.wait`` children (same thread): the host time of
    a step in which the chip has nothing of the step's to run."""
    steps = [s for s in et.spans
             if s.name == "engine.step" and lo <= s.start_ns < hi]
    if not steps:
        return None
    waits: Dict[str, List[Span]] = {}
    for s in et.spans:
        if s.name == "engine.wait":
            waits.setdefault(s.thread, []).append(s)
    starts = {t: [x.start_ns for x in w] for t, w in waits.items()}
    total = 0.0
    for s in steps:
        w = waits.get(s.thread, [])
        k = bisect.bisect_left(starts.get(s.thread, []), s.start_ns)
        total += s.dur_ns
        while k < len(w) and w[k].start_ns < s.end_ns:
            total -= w[k].dur_ns
            k += 1
    return total / len(steps) * 1e-6


def scoped_ms(et: EngineTrace, calls: Sequence, phases: Sequence[str]
              ) -> Optional[float]:
    """Device time per program execution (``calls``: events with
    ``start_ns`` and ``end_ns``, in time order) of the ops that start inside
    one and lie under any of the name scopes ``phases``."""
    ops = [o for o in et.ops
           if any(p in o.scope.split("/") for p in phases)]
    if not calls or not ops:
        return None
    starts = [c.start_ns for c in calls]
    total = 0.0
    for o in ops:
        k = bisect.bisect_right(starts, o.start_ns) - 1
        if k >= 0 and o.start_ns < calls[k].end_ns:
            total += o.dur_ns
    return total / len(calls) * 1e-6


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """Each device op's ``tf_op`` stat, keyed by the op's HLO text (the
    event metadata's name, which ``ProfileData`` gives as the event name;
    it gives events without their metadata's stats)."""
    out: Dict[str, str] = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:                              # XSpace.planes
            continue
        metas, stat_names = [], {}
        for f, v in _fields(plane):
            if f == 2 and not bytes(v).startswith((b"/device:TPU:",
                                                   b"/device:GPU:")):
                break                               # XPlane.name
            if f == 4:                              # XPlane.event_metadata
                metas.append(_entry(v))
            elif f == 5:                            # XPlane.stat_metadata
                sm = dict(_fields(_entry(v)))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        for md in metas:                            # XEventMetadata
            name, tf_op = "", ""
            for f, v in _fields(md):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 5:                        # XStat
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == "tf_op":
                        tf_op = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if tf_op:
                out[name] = tf_op
    return out


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a serialized protobuf message: an int for a
    varint, a memoryview for a length-delimited field, None for a fixed-width
    one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _entry(buf):
    """The value of a serialized map entry (field 2)."""
    return next((v for f, v in _fields(buf) if f == 2), b"")

"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

Each reader takes the run's context (``ctx``): the window record ``rec``
(host clock, the engine's counters, the benchmark's step log), the reduced
profiler ``trace`` and its window ``(lo, hi)`` in trace nanoseconds, the
configuration ``conf``, its reference module ``ref`` (which counts the
work a step needs) and the chip's ``peaks``.  A reader that finds nothing
to read returns None.
"""
from __future__ import annotations

from typing import Optional

import tracing

# the paged decode step program, by the jitted function's name in the
# module name
DECODE_PROGRAM = "paged_decode_step"
# the paged decode attention kernel, by its op name in the trace
DECODE_KERNEL = "paged_attention_decode"


def step_ms(ctx, program: str) -> Optional[float]:
    """Mean device time per execution of a step program."""
    calls = tracing.calls(ctx.trace, program)
    if not calls:
        return None
    return sum(e.dur_ns for e in calls) / len(calls) * 1e-6


def roofline_pct(ctx, kind: str, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's calls, over the time
    they took.  The k-th group of ``layers`` kernel events belongs to the
    k-th step of ``kind`` in the step log (the device runs steps in order);
    where the trace holds fewer, the last ones are paired."""
    evs = sorted(tracing.kernel_events(ctx.trace, kernel),
                 key=lambda e: e.start_ns)
    L = ctx.conf["num_hidden_layers"]
    steps = [rows for k, rows in ctx.rec.steps if k == kind]
    n = min(len(evs) // L, len(steps))
    if n == 0:
        return None
    evs = evs[len(evs) - n * L:]
    steps = steps[len(steps) - n:]
    bound = sum(L * ctx.peaks.bound_s(*ctx.ref.attention_call(ctx.conf, rows))
                for rows in steps)
    spent = sum(e.dur_ns for e in evs) * 1e-9
    return 100.0 * bound / spent if spent > 0 else None


def mfu_pct(ctx) -> Optional[float]:
    """Model FLOPs of the steps run in the window, over the window at the
    chip's peak."""
    rec = ctx.rec
    total = sum(ctx.ref.model_flops(ctx.conf, rows)
                for (_, rows), (t0, _) in zip(rec.steps, rec.step_times)
                if rec.t_open <= t0 < rec.t_close)
    if total == 0:
        return None
    return 100.0 * total / ((rec.t_close - rec.t_open) * ctx.peaks.flops)


def idle_pct(ctx) -> Optional[float]:
    lo, hi = ctx.window
    if not ctx.trace.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - tracing.busy_s(ctx.trace, lo, hi) / ((hi - lo) * 1e-9))


def occupancy_pct(ctx) -> Optional[float]:
    c = ctx.rec.counters
    steps = c.get("steps", 0)
    if not steps:
        return None
    return 100.0 * sum(c["shard_occupancy"]) / (steps * ctx.rec.batch)

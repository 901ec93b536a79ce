"""Drive the system under test through one measured window.

The window goes through the served path, ``StreamingServer.submit`` ->
``ServingEngine.step``.  This thread is the load generator: it keeps
``backlog`` requests pending beyond the batch.
"""
from __future__ import annotations

import contextlib
import time
from typing import List

import numpy as np


class StepLog:
    """The benchmark's own record of every engine step: one row per live
    request, ``(start, ntok, samples)``, read from the scheduler's slots
    before and after the step.  Installed for traced runs only: the
    per-layer readers use it, and it is host work in every step."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: List[tuple] = []      # (kind, rows)
        self.times: List[tuple] = []      # host (start, end) of each step
        self._step = engine.step
        engine.step = self._traced_step

    def close(self):
        self.engine.step = self._step

    def _slots(self):
        return {s.rid: (s.pos, s.prefilling, len(s.prompt))
                for _, s in self.engine.scheduler.active_slots()}

    def _traced_step(self):
        before = self._slots()
        t0 = time.monotonic()
        with _annotation("bench.step"):
            done = self._step()
        self.times.append((t0, time.monotonic()))
        after = self._slots()
        finished = {r.rid: r for r in done}
        rows, chunk = [], False
        for rid in set(before) | set(after):
            pos0, pre0, plen = before.get(rid, (0, True, None))
            if rid in after:
                pos1, pre1, plen = after[rid]
            elif rid in finished and plen is not None:
                # retired in this step: a decode token, or a last chunk
                pos1 = plen if pre0 else pos0 + 1
                pre1 = False
            else:
                continue                   # cancelled between steps
            ntok = pos1 - pos0
            if ntok <= 0:
                continue
            chunk |= pre0
            rows.append((pos0, ntok, not pre1))
        self.steps.append(("chunk" if chunk else "decode", rows))
        return done


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Window:
    """What the window saw, on the host's monotonic clock."""

    def __init__(self):
        self.t_open = self.t_close = 0.0
        self.handles: list = []
        self.requests: list = []
        self.rejected = 0


def submit(w: Window, server, r, annotate: bool = False) -> None:
    from repro.serve.engine import GenRequest
    from repro.serve.scheduler import RejectedError

    gen = GenRequest(prompt=r.prompt, max_new=r.max_new, seed=r.index)
    try:
        with (_annotation("bench.submit") if annotate
              else contextlib.nullcontext()):
            h = server.submit(gen)
    except RejectedError:
        w.rejected += 1
        return
    w.handles.append(h)
    w.requests.append(r)


def start_in_flight(server, reqs, batch: int, mix: dict) -> Window:
    """Set-up of an ``in_flight`` mix: submit the first ``batch`` requests
    and wait until each has its first token, so the window opens on a full
    batch already decoding.  Returns the window record they start."""
    w = Window()
    if not mix.get("in_flight", False):
        return w
    for r in reqs[:batch]:
        submit(w, server, r)
    for h in w.handles:
        while not h.token_times and not h.done:
            time.sleep(0.005)
    return w


def run_window(server, engine, reqs, mix: dict, seconds: float,
               w: Window, annotate: bool = False) -> Window:
    """Offer ``reqs`` (after those already in ``w``) for ``seconds``
    through ``server``.  The server keeps running afterwards; the caller
    stops it."""
    keep = engine.batch_size + int(mix["backlog"])
    i = len(w.handles)
    w.t_open = time.monotonic()
    w.t_close = w.t_open + seconds
    while True:
        now = time.monotonic()
        if now >= w.t_close:
            break
        live = sum(1 for h in w.handles if not h.done)
        while live < keep:
            r = reqs[i % len(reqs)]
            submit(w, server, r._replace(index=i), annotate)
            i += 1
            live += 1
        time.sleep(0.002)
    return w


def tokens_in_window(w: Window) -> int:
    return sum(int(np.sum((np.asarray(h.token_times) >= w.t_open)
                          & (np.asarray(h.token_times) < w.t_close)))
               for h in w.handles)


def finished(w: Window):
    """(request, GenResult) of every request that completed."""
    out = []
    for h, r in zip(w.handles, w.requests):
        if h.done:
            res = h.result(timeout=0)
            if res.done_reason in ("max_new", "eos"):
                out.append((r, res))
    return out

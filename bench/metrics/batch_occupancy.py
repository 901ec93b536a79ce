"""Scheduler: share of batch rows holding a live request, per engine step
(engine counters)."""
import readers


def read(ctx):
    return readers.occupancy_pct(ctx)

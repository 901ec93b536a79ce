"""Device: share of the window in which no operation ran on the chip
(trace)."""
import readers


def read(ctx):
    return readers.idle_pct(ctx)

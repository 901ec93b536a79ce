"""Model: model FLOPs of the real tokens stepped in the window over the
window at the chip's bf16 peak."""
import readers


def read(ctx):
    return readers.mfu_pct(ctx)

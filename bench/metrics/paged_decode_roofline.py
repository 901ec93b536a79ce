"""Kernels: the paged decode attention kernel's roofline bound over its
device time."""
import readers


def read(ctx):
    return readers.roofline_pct(ctx, "decode",
                                readers.DECODE_KERNEL)

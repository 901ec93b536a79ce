"""Engine step: device time per execution of the paged decode step program
(trace)."""
import readers


def read(ctx):
    return readers.step_ms(ctx, readers.DECODE_PROGRAM)

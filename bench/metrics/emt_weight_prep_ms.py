"""EMT simulation: device time per execution of the paged decode step of the
ops that depend only on the weights, those scoped ``emt.weights`` (quantize)
or ``emt.energy`` (the accounting's weight norms) (trace)."""
import engine_trace
import readers
import tracing


def read(ctx):
    et = engine_trace.of(ctx)
    if et is None:
        return None
    return engine_trace.scoped_ms(
        et, tracing.calls(ctx.trace, readers.DECODE_PROGRAM),
        ("emt.weights", "emt.energy"))

"""Engine host: host time of an engine step in which the chip has nothing of
the step's to run, the ``engine.step`` span less its ``engine.wait`` child;
mean over the steps that start in the window (trace)."""
import engine_trace


def read(ctx):
    et = engine_trace.of(ctx)
    return None if et is None else engine_trace.step_host_ms(et, *ctx.window)

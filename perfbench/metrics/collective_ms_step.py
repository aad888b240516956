"""Device time in all-reduce / all-gather / reduce-scatter operations per
optimizer step, averaged over the chips (trace)."""

from ..harness.trace_reduce import collectives


def read(ctx):
    trace, steps = ctx.get("trace"), ctx.get("trace_steps")
    found = collectives(trace) if trace is not None and steps else None
    return 1e3 * found["collective_s"] / steps if found else None

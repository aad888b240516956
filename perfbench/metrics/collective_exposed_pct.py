"""The part of the collective time during which no other operation ran on
that chip, over the traced window (trace)."""

from ..harness.trace_reduce import collectives


def read(ctx):
    trace = ctx.get("trace")
    found = collectives(trace) if trace is not None else None
    if not found:
        return None
    return 100.0 * found["exposed_s"] / ctx["busy"]["window_s"]

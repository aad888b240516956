"""Device self time per optimizer step and chip of the operations whose scope
says backward (a ``transpose(...)`` component), through the scope map
(harness/scope_reduce.py)."""

from ..harness.scope_reduce import phase_ms


def read(ctx):
    return phase_ms(ctx, "bwd")

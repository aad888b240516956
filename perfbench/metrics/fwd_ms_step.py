"""Device self time per optimizer step and chip of the operations whose scope
says forward (a ``jvp(...)`` component and no ``transpose(...)``): the model's
forward pass and the loss, through the scope map (harness/scope_reduce.py)."""

from ..harness.scope_reduce import phase_ms


def read(ctx):
    return phase_ms(ctx, "fwd")

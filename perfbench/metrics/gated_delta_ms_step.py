"""Device self time per optimizer step and chip under scope ``gated_delta``:
the delta rule's scan of every linear-attention operator, forward
(``remat``'s second one too) and backward, whatever implements it."""

from ..harness.olmo_hybrid_trace import part_ms


def read(ctx):
    return part_ms(ctx, "gated_delta")

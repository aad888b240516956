"""Device self time per optimizer step and chip under the linear-attention
operators (module ``linear_attention``): the projections, the convolutions
and l2 norms, the scan and the gated norm, forward (``remat``'s second one
too) and backward."""

from ..harness.olmo_hybrid_trace import MODULE, PARTS, part_ms


def read(ctx):
    return part_ms(ctx, MODULE, *PARTS)

"""Device self time per optimizer step and chip under the expert layers'
``mlp`` scope, forward and backward: router, dispatch, the grouped matmuls,
the shared expert, combine and what lies between them."""

from ..harness.joyai_trace import EXPERT_PARTS, part_ms


def read(ctx):
    return part_ms(ctx, *EXPERT_PARTS, "other")

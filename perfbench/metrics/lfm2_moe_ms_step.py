"""Device self time per optimizer step and chip under the expert layers'
``mlp`` scope, forward and backward: router, dispatch, the grouped matmuls,
combine and what lies between them (this trunk has no shared expert)."""

from ..harness.lfm2_trace import EXPERT_PARTS, part_ms


def read(ctx):
    return part_ms(ctx, *EXPERT_PARTS, "other")

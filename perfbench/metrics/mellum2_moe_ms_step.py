"""Device self time per optimizer step and chip under the expert layers'
``mlp`` scope of the ``mellum`` trunk, forward and backward: router,
dispatch, the grouped matmuls, combine and what lies between them (no shared
expert, every layer an expert layer). The twin of ``lfm2_moe_ms_step``."""

from ..harness.mellum2_trace import EXPERT_PARTS, part_ms


def read(ctx):
    return part_ms(ctx, *EXPERT_PARTS, "other")

"""Device time of the causal attention kernels at 30 heads of 128, no
rotation (``ops/flash_causal.py``: ``%flash_causal_fwd`` /
``%flash_causal_bwd*``) per optimizer step and chip, ``remat``'s second
forward call too. The twin of ``gqa_attn_ms_step`` for the ``olmo_hybrid``
trunk."""

from ..harness.olmo_hybrid_trace import part_ms


def read(ctx):
    return part_ms(ctx, "causal_kernels")

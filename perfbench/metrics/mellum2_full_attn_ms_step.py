"""Device time of the causal attention kernels (``ops/flash_causal.py``:
``%flash_causal_fwd`` / ``%flash_causal_bwd*``) per optimizer step and chip
in the ``mellum`` trunk: its full-attention layers, 32 query over 4 key/value
heads of 128. The twin of ``gqa_attn_ms_step``."""

from ..harness.mellum2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "causal_kernels")

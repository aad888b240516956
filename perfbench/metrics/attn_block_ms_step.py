"""Device self time per optimizer step and chip of the attention block less
its kernels, forward and backward: projections, bias and mask, dropout, the
block's LayerNorm (scope ``attention`` and neither ``flash_fwd`` nor
``flash_bwd``; the kernels stay ``attn_ms_step``'s)."""

from ..harness.scope_reduce import block_ms


def read(ctx):
    return block_ms(ctx, "attention")

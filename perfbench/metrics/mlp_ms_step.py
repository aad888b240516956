"""Device self time per optimizer step and chip of the feed-forward block
(scope ``mlp``), forward and backward: both matmuls, GELU, dropout, the
block's LayerNorm."""

from ..harness.scope_reduce import block_ms


def read(ctx):
    return block_ms(ctx, "mlp")

"""Model utilisation, not a roofline share: non-pad tokens per second per
chip times the matmul FLOPs the forward and backward passes need per token,
over the chip's bf16 peak."""

from ..harness.flops import matmul_flops_per_token


def read(ctx):
    if not ctx.get("train"):
        return None
    per_token = matmul_flops_per_token(
        ctx["cell"].config, ctx["seq_len"], train=True)
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * ctx["token_rate_chip"] * per_token / peak

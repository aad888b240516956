"""Model utilisation of the ``olmo_hybrid`` trunk, the share of the whole
step: non-pad tokens per second per chip times the matmul FLOPs a trained
token needs as the trunk is held here (``harness/flops_olmo_hybrid.py``:
causal pairs, the recurrence's own products, nothing recomputed: ``remat``'s
second forward is not needed work and lowers it), over the chip's bf16 peak.
Not a roofline share."""

from ..harness import flops_olmo_hybrid, olmo_hybrid_trace


def read(ctx):
    if not ctx.get("train") or not olmo_hybrid_trace.scan_layers(ctx):
        return None
    per_token = flops_olmo_hybrid.matmul_flops_per_token(
        ctx["cell"].config, ctx["seq_len"], train=True)
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * ctx["token_rate_chip"] * per_token / peak

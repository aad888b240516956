"""Model utilisation of the ``lfm2_moe`` trunk, the share of the whole step:
non-pad tokens per second per chip times the matmul FLOPs a trained token
needs as the trunk is held here (``harness/flops_lfm2.py``: causal pairs, the
held assignments the routing counter saw, nothing recomputed), over the
chip's bf16 peak. Not a roofline share."""

from ..harness import flops_lfm2, lfm2_trace


def read(ctx):
    cfg = ctx["cell"].config if "cell" in ctx else {}
    if not ctx.get("train") or "layer_types" not in cfg:
        return None
    held = lfm2_trace.held_per_step(ctx)
    if held is None:        # the program has no routing counter
        return None
    stretch = ctx["stretch"]
    tokens_step = stretch.all_tokens / max(stretch.steps, 1)
    per_token = flops_lfm2.matmul_flops_per_token(
        cfg, ctx["seq_len"], train=True,
        held_per_token=held / tokens_step / lfm2_trace.expert_layers(ctx))
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * ctx["token_rate_chip"] * per_token / peak

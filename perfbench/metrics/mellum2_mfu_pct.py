"""Model utilisation of the ``mellum`` trunk, the share of the whole step:
non-pad tokens per second per chip times the matmul FLOPs a trained token
needs as the trunk is held here (``harness/flops_mellum2.py``: a window row
counted at the pairs its band permits, a causal row at its triangle, the held
assignments the routing counter saw, nothing recomputed), over the chip's
bf16 peak. Not a roofline share."""

from ..harness import flops_mellum2, mellum2_trace


def read(ctx):
    if not ctx.get("train") or not mellum2_trace.layers(
            ctx, "sliding_attention"):
        return None
    held = mellum2_trace.held_per_step(ctx)
    if held is None:        # the program has no routing counter
        return None
    cfg = ctx["cell"].config
    stretch = ctx["stretch"]
    tokens_step = stretch.all_tokens / max(stretch.steps, 1)
    per_token = flops_mellum2.matmul_flops_per_token(
        cfg, ctx["seq_len"], train=True,
        held_per_token=held / tokens_step / len(cfg["layer_types"]))
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * ctx["token_rate_chip"] * per_token / peak

"""Model utilisation of the ``joyai_llm_flash`` trunk, not a roofline share:
non-pad tokens per second per chip times the matmul FLOPs a trained token
needs as the trunk is held here (``harness/flops_joyai.py``: causal pairs, the
held assignments the routing counter saw), over the chip's bf16 peak."""

from ..harness import flops_joyai, joyai_trace


def read(ctx):
    cfg = ctx["cell"].config if "cell" in ctx else {}
    if not ctx.get("train") or "experts_held" not in cfg:
        return None
    held = joyai_trace.held_per_step(ctx)
    if held is None:        # the program has no routing counter
        return None
    stretch = ctx["stretch"]
    tokens_step = stretch.all_tokens / max(stretch.steps, 1)
    expert_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_token = flops_joyai.matmul_flops_per_token(
        cfg, ctx["seq_len"], train=True,
        held_per_token=held / tokens_step / expert_layers)
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * ctx["token_rate_chip"] * per_token / peak

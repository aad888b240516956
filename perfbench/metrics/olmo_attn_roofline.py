"""The causal attention kernels' share of their roofline at 30 heads of 128:
the least time the chip could take for the causal cores of the traced steps
(the larger of FLOPs over the bf16 peak and bytes over the HBM peak, from
shapes, causal pairs only, nothing recomputed: ``harness/
flops_olmo_hybrid.py``) over the time the kernels took. The twin of
``gqa_attn_roofline`` for the ``olmo_hybrid`` trunk."""

from ..harness import flops_olmo_hybrid, olmo_hybrid_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = olmo_hybrid_trace.part_ms(ctx, "causal_kernels")
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    least = 0.0
    for rows, seq in ctx["trace_shapes"]:
        rows_chip = rows / ctx["chips"]
        least += olmo_hybrid_trace.attention_layers(ctx) * roofline_seconds(
            flops_olmo_hybrid.causal_core_flops(cfg, rows_chip, seq,
                                                train=ctx["train"]),
            flops_olmo_hybrid.causal_core_bytes(cfg, rows_chip, seq,
                                                train=ctx["train"]),
            ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])

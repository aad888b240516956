"""The causal attention kernels' share of their roofline at grouped-query
heads: the least time the chip could take for the causal cores of the traced
steps (the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
from shapes, causal pairs only, k/v bytes once a key/value head:
``harness/flops_lfm2.py``) over the time the kernels took."""

from ..harness import flops_lfm2, lfm2_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = lfm2_trace.part_ms(ctx, "causal_kernels")
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    least = 0.0
    for rows, seq in ctx["trace_shapes"]:
        rows_chip = rows / ctx["chips"]
        least += lfm2_trace.attention_layers(ctx) * roofline_seconds(
            flops_lfm2.causal_core_flops(cfg, rows_chip, seq,
                                         train=ctx["train"]),
            flops_lfm2.causal_core_bytes(cfg, rows_chip, seq,
                                         train=ctx["train"]),
            ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])

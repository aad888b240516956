"""The gating and taps' share of their roofline: the least time the chip
could take for them over the traced steps (the larger of bytes over the HBM
peak and elementwise FLOPs over the bf16 peak, from shapes:
``harness/flops_lfm2.py``; the bytes bound it) over the device self time
under scope ``short_conv``: the same work whatever implements it."""

from ..harness import flops_lfm2, lfm2_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = lfm2_trace.part_ms(ctx, "short_conv")
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    tokens = sum(rows * seq for rows, seq in ctx["trace_shapes"]) \
        / ctx["chips"]
    least = lfm2_trace.conv_layers(ctx) * roofline_seconds(
        flops_lfm2.short_conv_flops(cfg, tokens, train=ctx["train"]),
        flops_lfm2.short_conv_bytes(cfg, tokens, train=ctx["train"]),
        ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])

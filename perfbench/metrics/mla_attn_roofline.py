"""The causal attention kernels' share of their roofline: the least time the
chip could take for the causal cores of the traced steps (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, from shapes, causal
pairs only) over the time the kernels took."""

from ..harness.flops import roofline_seconds
from ..harness.flops_joyai import causal_core_bytes, causal_core_flops
from ..harness.joyai_trace import part_ms


def read(ctx):
    took_ms = part_ms(ctx, "causal_kernels")
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    least = 0.0
    for rows, seq in ctx["trace_shapes"]:
        rows_chip = rows / ctx["chips"]
        least += cfg["num_hidden_layers"] * roofline_seconds(
            causal_core_flops(cfg, rows_chip, seq, train=ctx["train"]),
            causal_core_bytes(cfg, rows_chip, seq, train=ctx["train"]),
            ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])

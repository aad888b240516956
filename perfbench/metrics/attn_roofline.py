"""The attention kernels' share of their roofline: the least time the chip
could take for the attention cores of the traced steps (the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, from shapes) over the time the
kernels took. An earlier line says which bound."""

from ..harness.flops import attention_bytes, attention_flops, roofline_seconds
from ..harness.trace_reduce import kernel_seconds
from .attn_ms_step import ATTENTION_KERNELS


def least_seconds(ctx):
    cfg = ctx["cell"].config
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    least, bound = 0.0, None
    for rows, seq in ctx["trace_shapes"]:
        rows_chip = rows / ctx["chips"]
        t, bound = roofline_seconds(
            attention_flops(rows_chip, seq, heads, head_dim, train=ctx["train"]),
            attention_bytes(rows_chip, seq, heads, head_dim, train=ctx["train"]),
            ctx["peaks"])
        least += cfg["num_hidden_layers"] * t
    return least, bound


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("trace_shapes"):
        return None
    took = kernel_seconds(trace, ATTENTION_KERNELS)
    if not took:
        return None
    return 100.0 * least_seconds(ctx)[0] / took

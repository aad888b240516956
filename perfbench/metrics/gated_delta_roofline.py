"""The delta rule's share of its roofline: the least time the chip could take
for the recurrences of the traced steps (the larger of FLOPs over the bf16
peak and bytes over the HBM peak, from shapes: ``harness/
flops_olmo_hybrid.py``: four products of ``d_k x d_v`` a token and head
forward, inputs read and outputs written once each way; the bytes bound it)
over the device self time under scope ``gated_delta``: the same work whatever
implements it, and nothing recomputed counts."""

from ..harness import flops_olmo_hybrid, olmo_hybrid_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = olmo_hybrid_trace.part_ms(ctx, "gated_delta")
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    tokens = sum(rows * seq for rows, seq in ctx["trace_shapes"]) \
        / ctx["chips"]
    least = olmo_hybrid_trace.scan_layers(ctx) * roofline_seconds(
        flops_olmo_hybrid.gated_delta_flops(cfg, tokens, train=ctx["train"]),
        flops_olmo_hybrid.gated_delta_bytes(cfg, tokens, train=ctx["train"]),
        ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])

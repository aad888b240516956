"""The routed experts' grouped matmuls' share of their roofline: the least
time the chip could take for the assignments the routing counter saw (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak,
``harness/flops_lfm2.py``) over the device self time under the ``experts``
scope and of the ``%ragged-dot*`` kernels, whatever implements them."""

from ..harness import flops_lfm2, lfm2_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = lfm2_trace.part_ms(ctx, "experts")
    held = lfm2_trace.held_per_step(ctx)
    if not took_ms or held is None or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    rows, _ = ctx["trace_shapes"][0]
    micro_batches = rows / ctx["chips"] / max(ctx["micro_rows_chip"], 1)
    least = roofline_seconds(
        flops_lfm2.grouped_matmul_flops(cfg, held, train=ctx["train"]),
        flops_lfm2.grouped_matmul_bytes(
            cfg, held, lfm2_trace.expert_layers(ctx) * micro_batches,
            train=ctx["train"]),
        ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3)

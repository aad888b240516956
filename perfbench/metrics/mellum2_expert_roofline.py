"""The routed experts' grouped matmuls' share of their roofline in the
``mellum`` trunk (experts of width 896): the least time the chip could take
for the assignments the routing counter saw (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, ``harness/flops_mellum2.py``) over the
device self time under the ``experts`` scope and of the ``%ragged-dot*``
kernels. The twin of ``lfm2_expert_roofline``."""

from ..harness import flops_mellum2, mellum2_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = mellum2_trace.part_ms(ctx, "experts")
    held = mellum2_trace.held_per_step(ctx)
    if not took_ms or held is None or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    rows, _ = ctx["trace_shapes"][0]
    micro_batches = rows / ctx["chips"] / max(ctx["micro_rows_chip"], 1)
    least = roofline_seconds(
        flops_mellum2.grouped_matmul_flops(cfg, held, train=ctx["train"]),
        flops_mellum2.grouped_matmul_bytes(
            cfg, held, len(cfg["layer_types"]) * micro_batches,
            train=ctx["train"]),
        ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3)

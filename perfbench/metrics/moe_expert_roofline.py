"""The routed experts' grouped matmuls' share of their roofline: the least
time the chip could take for the assignments the routing counter saw (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak,
``harness/flops_joyai.py``) over the device self time under the ``experts``
scope, whatever implements them."""

from ..harness import flops_joyai, joyai_trace
from ..harness.flops import roofline_seconds


def read(ctx):
    took_ms = joyai_trace.part_ms(ctx, "experts")
    held = joyai_trace.held_per_step(ctx)
    if not took_ms or held is None or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    expert_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows, _ = ctx["trace_shapes"][0]
    micro_batches = rows / ctx["chips"] / max(ctx["micro_rows_chip"], 1)
    least = roofline_seconds(
        flops_joyai.grouped_matmul_flops(cfg, held, train=ctx["train"]),
        flops_joyai.grouped_matmul_bytes(
            cfg, held, expert_layers * micro_batches, train=ctx["train"]),
        ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3)

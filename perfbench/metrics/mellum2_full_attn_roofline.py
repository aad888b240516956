"""The causal attention kernels' share of their roofline at 32/4 heads of
128: the least time the chip could take for the full-attention layers' cores
of the traced steps (causal pairs only: ``harness/flops_mellum2.py``) over
the time the kernels took. The twin of ``gqa_attn_roofline``."""

from ..harness.mellum2_trace import core_roofline_pct


def read(ctx):
    return core_roofline_pct(ctx, "full_attention", "causal_kernels")

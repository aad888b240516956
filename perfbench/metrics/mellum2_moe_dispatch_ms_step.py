"""Device self time per optimizer step and chip of the ``mellum`` trunk's
expert layers' parts that are no matmul of an expert: ``router`` (logits,
softmax, top-k), ``dispatch`` (the sort and the row gather) and ``combine``
(the weighted sum back). The twin of ``lfm2_moe_dispatch_ms_step``."""

from ..harness.mellum2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "router", "dispatch", "combine")

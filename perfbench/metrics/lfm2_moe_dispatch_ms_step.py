"""Device self time per optimizer step and chip of the expert layer's parts
that are no matmul of an expert: ``router`` (scores, top-k), ``dispatch``
(the sort and the row gather) and ``combine`` (the weighted sum back)."""

from ..harness.lfm2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "router", "dispatch", "combine")

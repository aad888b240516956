"""100 x the tokens of the fullest held expert over the mean of the held
experts in the ``mellum`` trunk (``train_moe_load_max_over_mean``, the median
of the telemetry stretch); 100 is an even load. The twin of
``lfm2_moe_load_max_pct``."""

from ..harness import mellum2_trace
from .moe_load_max_over_mean import read as ratio


def read(ctx):
    if "cell" not in ctx or not mellum2_trace.layers(
            ctx, "sliding_attention"):
        return None
    value = ratio(ctx)
    return None if value is None else 100.0 * value

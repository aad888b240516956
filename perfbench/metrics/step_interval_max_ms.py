"""The longest time between two consecutive step boundaries
(``train_step_interval_seconds``, ``quantile(1.0)`` of its reservoir): a
pause of the host shows here and not in the median."""

from perfbench.harness.span_record import interval_ms


def read(ctx):
    return interval_ms(ctx, 1.0)

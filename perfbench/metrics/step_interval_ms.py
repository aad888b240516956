"""Median time between two consecutive step boundaries
(``train_step_interval_seconds``: the unblocked step clock over every
stretch run without telemetry, the blocked wall in the telemetry stretch)."""

from perfbench.harness.span_record import interval_ms


def read(ctx):
    return interval_ms(ctx, 0.5)

"""Median host time to collate, split and place one global batch
(``train_step_host_seconds``; on the prefetch thread when prefetch is on)."""

from .step_ms import read as _median_ms


def read(ctx):
    return _median_ms(ctx, "train_step_host_seconds")

"""The HBM pre-flight, every attempt: the step traced, lowered and compiled
(or read from the cache) at each ``batch_split`` tried
(``train_setup_preflight_seconds``, span ``setup:preflight``)."""

from perfbench.harness.span_record import setup_seconds


def read(ctx):
    return setup_seconds(ctx, "preflight")

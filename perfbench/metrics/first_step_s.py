"""First batch in hand to the first step's outputs ready, the pre-flight not
included (``train_setup_first_step_seconds``, span ``setup:first_step``)."""

from perfbench.harness.span_record import setup_seconds


def read(ctx):
    return setup_seconds(ctx, "first_step")

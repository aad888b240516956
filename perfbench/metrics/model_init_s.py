"""``compose.init_model``: weights from the seed, the tokenizer, the eager
programs of both (``train_setup_init_model_seconds``, span
``setup:init_model``)."""

from perfbench.harness.span_record import setup_seconds


def read(ctx):
    return setup_seconds(ctx, "init_model")

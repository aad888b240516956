"""Tracing and lowering up to the end of the first step, which no compile
cache saves (``train_setup_trace_lower_seconds``: records ``compile:trace``
and ``compile:lower``, the compiles nested in them left out)."""

from perfbench.harness.span_record import setup_seconds


def read(ctx):
    return setup_seconds(ctx, "trace_lower")

"""Device self time per optimizer step and chip under the trainer's own step
phases (``grad_accumulate``, ``grad_reduce``, ``grad_clip``, ``optimizer``,
``step_metrics``): gradient carry, exchange, clip, AdamW, parameter cast."""

from ..harness.scope_reduce import phase_ms


def read(ctx):
    return phase_ms(ctx, "update")

"""Device time of the Pallas attention kernels per optimizer step and chip:
the sum of the trace's events whose name matches ``ATTENTION_KERNELS``."""

from ..harness.trace_reduce import kernel_seconds

# A Mosaic kernel is a ``%tpu_custom_call.<n>`` in a trace of this
# installation and carries no kernel name (read by hand, PR 22). With
# ``ln_impl=xla`` and no int8 path the attention kernels of
# ops/flash_attention.py are the only Pallas kernels in the step and in the
# serving forward: 2 x layers x micro-batches calls a step.
ATTENTION_KERNELS = r"^%tpu_custom_call"


def read(ctx):
    trace, steps = ctx.get("trace"), ctx.get("trace_steps")
    if trace is None or not steps:
        return None
    seconds = kernel_seconds(trace, ATTENTION_KERNELS)
    return 1e3 * seconds / steps if seconds else None

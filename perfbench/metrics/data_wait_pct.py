"""Share of the step wall the consumer spent blocked on the loader or the
prefetch queue: ``train_step_data_wait_seconds`` over ``train_step_seconds``
(``train/telemetry.py``'s partition, telemetry stretch only)."""

def read(ctx):
    reg = ctx.get("telemetry")
    if reg is None:
        return None
    wait = reg.get("train_step_data_wait_seconds")
    step = reg.get("train_step_seconds")
    if wait is None or step is None or not step.sum:
        return None
    return 100.0 * wait.sum / step.sum

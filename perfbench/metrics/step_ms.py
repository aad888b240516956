"""Median blocked step: dispatch to ``block_until_ready`` of its outputs
(``train_step_device_seconds``, telemetry stretch only)."""

def read(ctx, series="train_step_device_seconds"):
    reg = ctx.get("telemetry")
    hist = reg.get(series) if reg is not None else None
    if hist is None or not hist.count:
        return None
    return 1e3 * hist.quantile(0.5)

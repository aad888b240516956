"""1 - (union of the device-operation intervals) / (traced window), averaged
over the chips. From the device trace, never from host time."""

def read(ctx):
    busy = ctx.get("busy")
    return busy["idle_pct"] if busy else None

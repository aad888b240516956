"""Chunks the engine completed per second of the window (valid rows of the
launched batches, engine counters read before and after)."""

def read(ctx):
    c = ctx.get("counters")
    if not c or not c.get("window_s"):
        return None
    return c["chunks"] / c["window_s"]

"""Valid rows over bucket rows of the batches the engine launched in the
window (``qa_batch_occupancy``: sum over count, read before and after)."""

def read(ctx):
    c = ctx.get("counters")
    if not c or not c.get("batches"):
        return None
    return 100.0 * c["occupancy_sum"] / c["batches"]

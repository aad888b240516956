"""Pad tokens over all token slots of the batches the window consumed."""

def read(ctx):
    stretch = ctx.get("stretch")
    if stretch is None or not stretch.all_tokens:
        return None
    return 100.0 * (1.0 - stretch.real_tokens / stretch.all_tokens)

"""Programs obtained (compiled or read from the cache) after the window
opened. Anything but 0 makes the run incorrect."""

def read(ctx):
    return ctx["compile"]["window_compiles"]

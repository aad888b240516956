"""100 x the (q block, k block) pairs the sliding-window kernels' grids walk
a step over the pairs the causal triangle would have had them walk
(``train_attn_window_block_pairs`` over ``train_attn_causal_block_pairs``,
both from the kernels' tables, the medians of the telemetry stretch): 100
means the window cut nothing."""


def read(ctx):
    reg = ctx.get("telemetry")
    if reg is None:
        return None
    walked, causal = (reg.get(f"train_attn_{name}_block_pairs")
                      for name in ("window", "causal"))
    if walked is None or causal is None or not causal.count \
            or not causal.quantile(0.5):
        return None
    return 100.0 * walked.quantile(0.5) / causal.quantile(0.5)

"""Tokens of the fullest held expert over the mean of the held experts
(``train_moe_load_max_over_mean``: averaged over expert layers and
micro-batches by the program), the median of the telemetry stretch. The
routing is not stationary while the router trains, so the stretch's least,
median and greatest of this and of the held share go on an earlier line."""


def read(ctx):
    reg = ctx.get("telemetry")
    series = reg.get("train_moe_load_max_over_mean") if reg is not None \
        else None
    if series is None or not series.count:
        return None
    from perfbench.harness.result import note

    spread = lambda s: [s.quantile(q) for q in (0.0, 0.5, 1.0)]  # noqa: E731
    share = reg.get("train_moe_held_share")
    note(moe_routing_over_the_stretch={
        "steps": series.count, "load_max_over_mean": spread(series),
        "held_share": spread(share) if share is not None else None})
    return series.quantile(0.5)

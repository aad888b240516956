"""The program's own account of the host's time: the set-up gauges and the
step-interval histogram of ``train/telemetry.py`` (which reads them from the
always-on span record of ``metrics/trace.py``), and two earlier lines with the
tables behind them. A program without the series or the record (a parent
commit's) gives ``None`` and no line; so does a context with no telemetry."""

from __future__ import annotations

import statistics
import sys

from .result import note


def series(ctx, name):
    registry = ctx.get("telemetry")
    return registry.get(name) if registry is not None else None


def setup_seconds(ctx, name):
    """The gauge ``train_setup_<name>_seconds``; the ``setup_spans`` line goes
    out with the first of them."""
    gauge = series(ctx, f"train_setup_{name}_seconds")
    if gauge is None:
        return None
    _once(ctx, "setup_spans", _setup_table)
    return gauge.value


def interval_ms(ctx, q):
    """Quantile ``q`` of ``train_step_interval_seconds``' reservoir, in ms;
    the ``step_clock`` line goes out with the first reading."""
    histogram = series(ctx, "train_step_interval_seconds")
    if histogram is None or not histogram.count:
        return None
    _once(ctx, "step_clock", _step_table)
    return 1e3 * histogram.quantile(q)


def _once(ctx, key, table) -> None:
    if key in ctx.setdefault("span_notes", set()):
        return
    ctx["span_notes"].add(key)
    from ml_recipe_tpu.metrics.trace import recent

    said = table(ctx, recent())
    if said is not None:
        note(**{key: said})


def _clip(records, t0, t1):
    return [(max(r.t0, t0), min(r.t1, t1)) for r in records
            if r.t1 > t0 and r.t0 < t1]


def _setup_table(ctx, records):
    """Every set-up span with its self time (its length less the set-up spans
    inside it) and the tracing, lowering and compiling (or cache reading)
    inside it; then how much of process start to window open the spans
    cover. Process start is ``perfbench/run.py``'s ``T_START``; the window
    opens where the first epoch's hooks return (they stamp ``setup_s``)."""
    from ml_recipe_tpu.train.telemetry import STEP_PHASES, covered_seconds

    setup = [r for r in records if r.cat == "setup"]
    if not setup:
        return None
    stages = [r for r in records if r.cat == "compile"]
    compiles = [r for r in stages if r.name == "backend"]
    traces = [r for r in stages if r.name != "backend"]
    hooks = [r for r in records if r.cat == "train" and r.name == "after_epoch"]
    start = getattr(sys.modules.get("__main__"), "T_START", setup[0].t0)
    opened = hooks[0].t1 if hooks else max(r.t1 for r in setup)

    def compiling(t0, t1):
        backend = _clip(compiles, t0, t1)
        return {"trace_lower_s": covered_seconds(_clip(traces, t0, t1), backend),
                "backend_s": covered_seconds(backend)}

    rows = []
    for r in setup:
        inside = [c for c in setup if c is not r and c.thread == r.thread
                  and c.parent == f"setup:{r.name}"
                  and r.t0 <= c.t0 and c.t1 <= r.t1]
        rows.append({
            "span": r.name, "at_s": r.t0 - start, "s": r.seconds,
            "self_s": r.seconds - covered_seconds(
                [(c.t0, c.t1) for c in inside]),
            **compiling(r.t0, r.t1), **(r.args or {})})
    named = _clip(setup, start, opened)
    stepping = _clip([r for r in records
                      if r.cat == "train" and r.name in STEP_PHASES],
                     start, opened)
    first = min(r.t0 for r in setup)
    before_first = max(0.0, first - start)
    return {
        "start_to_window_s": opened - start,
        # imports, arguments, inputs from the seed, the backend's start
        "before_first_span_s": before_first,
        "before_first_span": compiling(start, first),
        "setup_spans_s": covered_seconds(named),
        "step_spans_s": covered_seconds(stepping, named),  # the warm-up's
        "uncovered_s": (opened - start) - before_first
        - covered_seconds(named + stepping),
        "compile_records": len(stages), "spans": rows}


def _step_table(ctx, records):
    """The unblocked step clock: how many steady intervals, their median, and
    for the five longest the step, the length and the phase of the loop that
    held most of it. The steps a telemetry blocked after are in the histogram
    by their walls and not on the clock: their longest goes beside."""
    from ml_recipe_tpu.train.telemetry import covering_phase, step_intervals

    clock = step_intervals(records)
    if not clock:
        return None
    longest = []
    for interval in sorted(clock, key=lambda i: i.seconds)[-5:][::-1]:
        phase, held = covering_phase(
            records, interval.t0, interval.t1, interval.thread)
        longest.append({"step": interval.step, "ms": 1e3 * interval.seconds,
                        "phase": phase, "held_ms": 1e3 * held})
    blocked = series(ctx, "train_step_seconds")
    return {
        "intervals": len(clock),
        "median_ms": 1e3 * statistics.median(i.seconds for i in clock),
        "longest": longest,
        "epochs": _epochs(records),
        "blocked_steps": blocked.count if blocked is not None else 0,
        "blocked_wall_max_ms": 1e3 * blocked.quantile(1.0)
        if blocked is not None and blocked.count else None}


def _epochs(records):
    """An epoch a row: its steps, and what its first two took from the first
    wait for a batch to the second boundary. The clock leaves those two out;
    a rate over the whole stretch does not."""
    # (a step's own wait, not the wait before it that found the data at its
    # end under the same number: the later of the two)
    waits = {r.args["step"]: r.t0 for r in records
             if r.cat == "train" and r.name == "data_wait"}
    epochs = {}
    for r in records:
        if r.cat == "train" and r.name == "consume":
            epochs.setdefault(r.args["epoch"], []).append(r)
    rows = []
    for epoch, boundaries in sorted(epochs.items()):
        boundaries.sort(key=lambda r: r.args["step"])
        head = boundaries[:2]
        rows.append({
            "epoch": epoch, "steps": len(boundaries),
            "blocked": bool(boundaries[0].args["blocked"]),
            "first_two_steps_s": head[-1].t1 - waits[head[0].args["step"]]
            if head[0].args["step"] in waits else None})
    return rows

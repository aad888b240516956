"""Training-plane telemetry: the trainer's /metrics surface.

The serving plane has had a first-party Prometheus registry since PR 3;
the training plane — the thing that runs for days on pod slices — was
observable only through tqdm postfix lines and the TensorBoard writer.
:class:`TrainTelemetry` gives it the same surface: a registry of training
metrics (served by ``metrics.exporter.MetricsExporter`` from
``--metrics_port``) fed per consumed step by the trainer with a wall-time
breakdown —

- ``data_wait``: blocked on the loader / prefetch queue,
- ``host``: collate + micro split + host→device placement,
- ``device``: step dispatch + device execution (the ``StepTimer``
  block-until-ready discipline, so async dispatch cannot fake it),

plus tokens/sec, padding waste, loss-scale adjustments, checkpoint
save/restore durations, and — at scrape time — the watchdog heartbeat age
and the supervisor's restart/exit-classification counts read cross-process
from its JSON sidecar (``resilience.supervisor.peek_supervisor_state``).

Everything here is opt-in and host-side-only: with no telemetry attached
the trainer's step loop is bit-identical to the untelemetered path, and
with it attached only timing/blocking changes — never batch contents,
order, or arithmetic.

Two families of series are read from the span plane's always-on record
(``metrics/trace.py``) and so also cover what ran with no telemetry
attached: the set-up gauges (``train_setup_*_seconds``) and the step clock
(``train_step_interval_seconds``); ``setup_seconds`` and ``step_intervals``
below are the two reductions.
"""

from __future__ import annotations

import logging
import math
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from ..metrics import trace
from ..metrics.anomaly import AnomalyReport, SlowStepDetector
from ..metrics.registry import Registry
from ..metrics.trace import SpanRecord

logger = logging.getLogger(__name__)

# step-scale histogram bounds: 5 ms .. 120 s (a pod-scale step with a
# checkpoint barrier in the tail is seconds, not the serving plane's ms)
STEP_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    120.0,
)

# one set for a share (0..1), a load ratio (1..experts held) and a count of
# assignments a step: the readers take quantiles from the reservoir
MOE_BUCKETS = (0.01, 0.03, 0.0625, 0.125, 0.25, 0.5, 1.0, 1.25, 1.5, 2.0, 4.0,
               16.0, 1e3, 1e4, 1e5, 1e6)

# checkpoint I/O is far slower than a step: 50 ms .. 10 min
CKPT_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0, 600.0)


# -- reductions of the span record -------------------------------------------------

# the spans of the train loop that follow one another on the consumer's thread
# (``place`` nests in ``data_wait`` when it runs inline); ``step`` overlaps them
# all and is no phase
STEP_PHASES = ("data_wait", "place", "dispatch", "consume", "after_epoch")
# steps at the head of an epoch whose intervals are no steady state: the first
# has no boundary before it, the second spans the pipeline's filling (and, in
# a run's first epoch, the compile)
EPOCH_HEAD_STEPS = 2


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for t0, t1 in sorted(intervals):
        if merged and t0 <= merged[-1][1]:
            if t1 > merged[-1][1]:
                merged[-1] = (merged[-1][0], t1)
        else:
            merged.append((t0, t1))
    return merged


def covered_seconds(intervals: Iterable[Tuple[float, float]],
                    holes: Iterable[Tuple[float, float]] = ()) -> float:
    """Length of the union of ``intervals`` outside the union of ``holes``."""
    spans, gaps = _union(intervals), _union(holes)
    total = sum(t1 - t0 for t0, t1 in spans)
    first = 0
    for h0, h1 in gaps:
        while first < len(spans) and spans[first][1] <= h0:
            first += 1
        at = first
        while at < len(spans) and spans[at][0] < h1:
            total -= min(spans[at][1], h1) - max(spans[at][0], h0)
            at += 1
    return total


def setup_seconds(records: List[SpanRecord]) -> Dict[str, float]:
    """The four set-up gauges from the ``setup`` and ``compile`` records of
    ``trace.recent()``: the newest ``init_model`` and ``first_step``, every
    ``preflight``, and the time inside a ``trace`` or ``lower`` record and
    outside every ``backend`` one (a kernel's compile probe runs while the
    step is traced) up to the end of ``first_step``. 0.0 for what never ran."""
    newest = {r.name: r for r in records if r.cat == "setup"}
    first_step = newest.get("first_step")
    until = first_step.t1 if first_step is not None else math.inf
    stages = [r for r in records if r.cat == "compile" and r.t1 <= until]
    return {
        "init_model": newest["init_model"].seconds
        if "init_model" in newest else 0.0,
        "preflight": sum(r.seconds for r in records
                         if r.cat == "setup" and r.name == "preflight"),
        "trace_lower": covered_seconds(
            [(r.t0, r.t1) for r in stages if r.name in ("trace", "lower")],
            [(r.t0, r.t1) for r in stages if r.name == "backend"]),
        "first_step": first_step.seconds if first_step is not None else 0.0,
    }


class StepInterval(NamedTuple):
    step: int
    seconds: float
    t0: float           # the boundary before, and this step's
    t1: float
    thread: int         # the loop's


def step_intervals(records: List[SpanRecord],
                   since: float = 0.0) -> List[StepInterval]:
    """The step clock: every steady step among the ``train`` records, by
    step. A step's boundary is the end of its ``consume`` span (the lagged
    fetch of its scalars returns when the step has finished), its interval
    the time since the boundary before it in the same epoch, and an epoch's
    first ``EPOCH_HEAD_STEPS`` steps are left out. So are the steps a
    telemetry blocked after (``observe_step`` has their walls; their fetch
    comes a step late and marks no boundary). ``since`` drops boundaries
    before that ``perf_counter`` reading (another trainer's)."""
    epochs: Dict[tuple, List[SpanRecord]] = {}
    for r in records:
        if (r.cat == "train" and r.name == "consume" and r.t1 >= since
                and not r.args["blocked"]):
            epochs.setdefault((r.thread, r.args["epoch"]), []).append(r)
    out = []
    for boundaries in epochs.values():
        boundaries.sort(key=lambda r: r.args["step"])
        for before, at in zip(boundaries[EPOCH_HEAD_STEPS - 1:],
                              boundaries[EPOCH_HEAD_STEPS:]):
            out.append(StepInterval(at.args["step"], at.t1 - before.t1,
                                    before.t1, at.t1, at.thread))
    return sorted(out)


def covering_phase(records: List[SpanRecord], t0: float, t1: float,
                   thread: int) -> Tuple[str, float]:
    """Which of ``STEP_PHASES`` held most of ``[t0, t1]`` on ``thread``, and
    for how many seconds; ``("uncovered", s)`` when the time no phase covers
    is the largest part. A ``place`` inside a ``data_wait`` counts for itself
    alone."""
    held: Dict[str, float] = {}
    spans = []
    for r in records:
        if (r.cat == "train" and r.name in STEP_PHASES and r.thread == thread
                and r.t1 > t0 and r.t0 < t1):
            inside = (max(r.t0, t0), min(r.t1, t1))
            spans.append(inside)
            held[r.name] = held.get(r.name, 0.0) + inside[1] - inside[0]
            outer = (r.parent or "").partition(":")[2]
            if outer in STEP_PHASES:
                held[outer] = held.get(outer, 0.0) - (inside[1] - inside[0])
    held["uncovered"] = (t1 - t0) - covered_seconds(spans)
    name = max(held, key=held.get)
    return name, held[name]


class TrainTelemetry:
    """Registry + per-step accounting + slow-step anomaly detection."""

    def __init__(
        self,
        *,
        registry: Optional[Registry] = None,
        process_index: int = 0,
        process_count: int = 1,
        anomaly_factor: float = 3.0,
        anomaly_window: int = 64,
        anomaly_min_steps: int = 8,
        watchdog=None,
        supervisor_state_path=None,
        goodput=None,
        flightrec=None,
    ):
        self.registry = registry if registry is not None else Registry()
        self.watchdog = watchdog
        self.supervisor_state_path = (
            str(supervisor_state_path) if supervisor_state_path else None
        )
        # run-level accounting plane (PR 13), both optional and host-only:
        # goodput is a metrics.goodput.GoodputLedger (productive-vs-badput
        # wall-clock partition, exported as train_goodput_ratio), flightrec
        # a metrics.flightrec.FlightRecorder (last-N-events crash timeline)
        self.goodput = goodput
        self.flightrec = flightrec
        self._observed_steps = 0
        self._aot_hits = 0
        self._aot_misses = 0
        self.detector = SlowStepDetector(
            factor=anomaly_factor,
            window=anomaly_window,
            min_steps=anomaly_min_steps,
        )
        self._last_loss_scale: Optional[float] = None
        # the newest global step whose interval reached m_step_interval
        self._interval_step = -1

        m = self.registry
        self.m_steps = m.counter(
            "train_steps_total", "Consumed optimizer steps this process.")
        self.m_global_step = m.gauge(
            "train_global_step", "Current global optimizer step.")
        self.m_step = m.histogram(
            "train_step_seconds",
            "Per-step wall time: data wait + host + device (host excluded "
            "when the prefetch thread overlaps it with device compute).",
            STEP_BUCKETS)
        self.m_data_wait = m.histogram(
            "train_step_data_wait_seconds",
            "Per-step time blocked on the loader / prefetch queue.",
            STEP_BUCKETS)
        self.m_host = m.histogram(
            "train_step_host_seconds",
            "Per-step collate + micro split + host-to-device placement time.",
            STEP_BUCKETS)
        self.m_device = m.histogram(
            "train_step_device_seconds",
            "Per-step dispatch + device execution time (block-until-ready).",
            STEP_BUCKETS)
        self.m_step_interval = m.histogram(
            "train_step_interval_seconds",
            "Time between two consecutive step boundaries inside an epoch, "
            "its first two steps left out: from the unblocked step clock "
            "for steps run with no telemetry attached, the blocked step "
            "wall for the rest.", STEP_BUCKETS)
        self.m_setup = {
            "init_model": m.gauge(
                "train_setup_init_model_seconds",
                "compose.init_model: weights from the seed or a checkpoint, "
                "tokenizer (span setup:init_model; 0: not run here)."),
            "preflight": m.gauge(
                "train_setup_preflight_seconds",
                "The HBM pre-flight, every attempt: trace, lower and compile "
                "or cache read of the step at each batch_split tried (span "
                "setup:preflight)."),
            "trace_lower": m.gauge(
                "train_setup_trace_lower_seconds",
                "Tracing and lowering up to the end of the first step, which "
                "no compile cache saves (records compile:trace and "
                "compile:lower, compiles nested in them left out)."),
            "first_step": m.gauge(
                "train_setup_first_step_seconds",
                "First batch in hand to the first step's outputs ready, the "
                "pre-flight not included (span setup:first_step)."),
        }
        self.m_tokens_per_sec = m.gauge(
            "train_tokens_per_sec",
            "Real (non-pad) input tokens per second, last consumed step.")
        self.m_examples_per_sec = m.gauge(
            "train_examples_per_sec",
            "Examples (rows / packed segments) per second, last step.")
        self.m_padding_waste = m.gauge(
            "train_padding_waste_pct",
            "Share of step input tokens that are padding, last step (%).")
        self.m_loss = m.gauge(
            "train_loss", "Running mean training loss (epoch meter).")
        self.m_lr = m.gauge(
            "train_lr", "Learning rate at the last consumed step.")
        self.m_loss_scale = m.gauge(
            "train_loss_scale",
            "Current loss scale (0 when loss scaling is off).")
        self.m_loss_scale_adjustments = m.counter(
            "train_loss_scale_adjustments_total",
            "Dynamic loss-scale changes (growth or overflow backoff).")
        self.m_slow_steps = m.counter(
            "train_slow_steps_total",
            "Steps flagged anomalous by the rolling median+MAD detector.")
        self.m_ckpt_save = m.histogram(
            "train_checkpoint_save_seconds",
            "Checkpoint save durations on the step critical path (sync "
            "saves: full serialize+write; async saves: the blocking "
            "device-to-host snapshot only).", CKPT_BUCKETS)
        self.m_ckpt_persist = m.histogram(
            "train_checkpoint_persist_seconds",
            "Background persist durations of async checkpoint saves "
            "(serialize+write overlapped with training — off the step "
            "critical path).", CKPT_BUCKETS)
        self.m_ckpt_restore = m.histogram(
            "train_checkpoint_restore_seconds",
            "Checkpoint restore durations.", CKPT_BUCKETS)
        self.m_zero1_buckets = m.gauge(
            "train_zero1_buckets",
            "Gradient buckets in the bucketed ZeRO-1 collective-overlap "
            "plan (0 = monolithic exchange / overlap off).")
        self.m_grad_exchanges = m.gauge(
            "train_grad_exchanges_per_step",
            "Gradient exchanges over the mesh data axis per optimizer step: "
            "1 = once, after the micro-batch loop; batch_split = after "
            "every micro-batch; 0 = no data axis wider than 1.")
        # a causal trunk's step counters (models/mla_moe.py): an expert-routed
        # one's, a linear-attention one's and a sliding-window one's; a model
        # without such layers never observes them
        self.m_moe = {
            "moe_held_assignments": m.histogram(
                "train_moe_held_assignments",
                "Token-to-expert assignments a step whose expert this "
                "process holds, summed over the expert layers.", MOE_BUCKETS),
            "moe_load_max_over_mean": m.histogram(
                "train_moe_load_max_over_mean",
                "Tokens of the fullest held expert over the mean of the "
                "held experts, averaged over layers and micro-batches.",
                MOE_BUCKETS),
            "moe_held_share": m.histogram(
                "train_moe_held_share",
                "Share of all token-to-expert assignments whose expert "
                "this process holds.", MOE_BUCKETS),
            "moe_overflow_chunks": m.histogram(
                "train_moe_overflow_chunks",
                "Granules of rows the expert layers took beyond their first "
                "chunk, summed over layers and micro-batches (0: every "
                "routing fitted the first chunk).", MOE_BUCKETS),
            "moe_filler_share": m.histogram(
                "train_moe_filler_share",
                "Share of the rows the expert layers processed that hold no "
                "assignment, averaged over layers and micro-batches.",
                MOE_BUCKETS),
            "moe_row_tile_fill": m.histogram(
                "train_moe_row_tile_fill",
                "Held rows over the rows of the row tiles the grouped "
                "matmuls' kernels visit (1: no tile cut by a group boundary "
                "or by filler), averaged over layers and micro-batches.",
                MOE_BUCKETS),
            "linear_decay_mean": m.histogram(
                "train_linear_decay_mean",
                "Mean decay exp(g) a token the linear-attention layers "
                "applied to their state, over real tokens, heads and "
                "layers.", MOE_BUCKETS),
            "linear_beta_mean": m.histogram(
                "train_linear_beta_mean",
                "Mean write strength beta of the linear-attention layers' "
                "delta rule, over real tokens, heads and layers (up to 2 "
                "with negative eigenvalues allowed).", MOE_BUCKETS),
            "attn_window_block_pairs": m.histogram(
                "train_attn_window_block_pairs",
                "(q block, k block) pairs the sliding-window attention "
                "kernels' grids walk a step, over rows, heads, layers and "
                "the forward and backward calls.", MOE_BUCKETS),
            "attn_causal_block_pairs": m.histogram(
                "train_attn_causal_block_pairs",
                "Pairs the same calls would walk under the causal triangle "
                "alone (no window).", MOE_BUCKETS),
        }
        self.m_aot_hits = m.counter(
            "train_aot_cache_hits_total",
            "AOT program-store loads that replaced an XLA compile "
            "(ops/aot.py: zero-compile warm restarts).")
        self.m_aot_misses = m.counter(
            "train_aot_cache_misses_total",
            "AOT program-store misses: programs compiled (and persisted "
            "for the next restart).")
        self.m_aot_load = m.histogram(
            "train_aot_load_seconds",
            "AOT program load (deserialize) times on store hits.",
            STEP_BUCKETS)
        self.m_heartbeat_age = m.gauge(
            "train_watchdog_heartbeat_age_seconds",
            "Seconds since the step watchdog last saw progress "
            "(-1: no watchdog armed).")
        self.m_sup_restarts = m.gauge(
            "train_supervisor_restarts",
            "Supervisor restart budget consumed (no-progress failures), "
            "from the supervisor JSON sidecar (-1: no sidecar).")
        self.m_sup_attempts = m.gauge(
            "train_supervisor_attempts",
            "Supervisor attempts launched so far (-1: no sidecar).")
        self.m_sup_preempted = m.gauge(
            "train_supervisor_exits_preempted",
            "Child exits the supervisor classified as preemptions.")
        self.m_sup_hang = m.gauge(
            "train_supervisor_exits_hang",
            "Child exits the supervisor classified as hangs "
            "(watchdog aborts).")
        self.m_sup_crash = m.gauge(
            "train_supervisor_exits_crash",
            "Child exits the supervisor classified as crashes.")
        self.m_goodput = m.gauge(
            "train_goodput_ratio",
            "Productive step time / total run wall-clock, from the goodput "
            "ledger (-1: no ledger attached).")
        self.m_badput = m.labeled_gauge(
            "train_badput_seconds_total",
            "Non-productive run wall-clock by category, from the goodput "
            "ledger (compile_warmup / data_wait / checkpoint_save / "
            "checkpoint_restore / eval / restart_downtime / recompute / "
            "other).",
            "category")
        self.m_process = m.info(
            "train_process_info",
            "Identity of this training process on the mesh.",
            {
                "process_index": str(process_index),
                "process_count": str(process_count),
            },
        )
        self.m_heartbeat_age.set(-1.0)
        self.m_sup_restarts.set(-1.0)
        self.m_sup_attempts.set(-1.0)
        self.m_goodput.set(-1.0)
        self.observe_span_record()

    # -- the span record (construction, and each epoch's start) -----------------

    def observe_span_record(self, since: Optional[float] = None) -> None:
        """Set the set-up gauges from the span plane's record and, given the
        ``perf_counter`` reading ``since`` which the trainer was built at,
        take in the step clock's intervals of the steps this telemetry has
        not seen: those run with no telemetry attached, each once."""
        for name, seconds in setup_seconds(
                trace.recent("setup") + trace.recent("compile")).items():
            self.m_setup[name].set(seconds)
        if since is None:
            return
        for interval in step_intervals(trace.recent("train"), since):
            if interval.step > self._interval_step:
                self.m_step_interval.observe(interval.seconds)
                self._interval_step = interval.step

    # -- per-step feed (train loop) --------------------------------------------

    def observe_step(
        self,
        step: int,
        *,
        data_wait_s: float,
        host_s: float,
        device_s: float,
        examples: int = 0,
        real_tokens: int = 0,
        total_tokens: int = 0,
        host_overlapped: bool = False,
        epoch_head: bool = False,
    ) -> Optional[AnomalyReport]:
        """Feed one consumed step's breakdown; total step time is defined
        as the sum of the components on the critical path (pinned by the
        accounting test). ``host_overlapped=True`` (the device-prefetch
        path) excludes ``host_s`` from the total and the detector
        baseline: placement ran on the prefetch thread UNDER the previous
        step's device time, so counting it would overstate the step wall
        — a prefetch thread that falls behind surfaces as data wait. The
        host histogram itself still records every placement.
        ``epoch_head=True`` (one of an epoch's first ``EPOCH_HEAD_STEPS``
        steps) keeps the wall out of the step-interval histogram, as the
        step clock leaves those steps out. Returns the anomaly report when
        the detector fired (already logged and counted here)."""
        total = data_wait_s + device_s
        breakdown = {"data_wait": data_wait_s, "device": device_s}
        if not host_overlapped:
            total += host_s
            breakdown["host"] = host_s
        self.m_steps.inc()
        self.m_global_step.set(step)
        self.m_step.observe(total)
        if not epoch_head:
            # blocked after every step, the time between boundaries IS the wall
            self.m_step_interval.observe(total)
        self._interval_step = max(self._interval_step, int(step))
        self.m_data_wait.observe(data_wait_s)
        self.m_host.observe(host_s)
        self.m_device.observe(device_s)
        if total > 0:
            if real_tokens:
                self.m_tokens_per_sec.set(real_tokens / total)
            if examples:
                self.m_examples_per_sec.set(examples / total)
        if total_tokens:
            self.m_padding_waste.set(
                100.0 * (1.0 - real_tokens / total_tokens))

        # goodput ledger: the first observed step carries compilation —
        # its non-wait share is compile/warmup badput, not productive time.
        # The aot_hit flag says whether that warmup was a store LOAD
        # (every observed program-store decision a hit) or a real compile
        first = self._observed_steps == 0
        self._observed_steps += 1
        if self.goodput is not None:
            aot_hit = None
            if first and (self._aot_hits or self._aot_misses):
                aot_hit = self._aot_misses == 0
            self.goodput.note_step(
                step, wall_s=total, data_wait_s=data_wait_s, compile=first,
                aot_hit=aot_hit,
            )

        report = self.detector.update(step, total, breakdown)
        if report is not None:
            self.m_slow_steps.inc()
            logger.warning(report.message())

        if self.flightrec is not None:
            heartbeat = (
                self.watchdog.heartbeat_age()
                if self.watchdog is not None else None
            )
            self.flightrec.record(
                "step", step=int(step), total_s=round(total, 6),
                data_wait_s=round(data_wait_s, 6),
                host_s=round(host_s, 6), device_s=round(device_s, 6),
                examples=int(examples),
                heartbeat_age_s=(
                    round(heartbeat, 3) if heartbeat is not None else None
                ),
            )
            if report is not None:
                # the anomaly verdict rides the ring too: attribution must
                # survive the crash that often follows a stall
                self.flightrec.record(
                    "slow_step", step=report.step,
                    total_s=round(report.total_s, 6),
                    threshold_s=round(report.threshold_s, 6),
                    attribution=report.attribution,
                    component_s=round(report.component_s, 6),
                )
        return report

    def observe_aot(self, outcome: str, seconds: float) -> None:
        """One AOT program-store decision from the trainer's routing
        (ops/aot.py): ``'hit'`` = deserialized (the load-time histogram
        records it), ``'miss'`` = compiled. Bypass decisions (store off)
        never reach here."""
        if outcome == "hit":
            self._aot_hits += 1
            self.m_aot_hits.inc()
            self.m_aot_load.observe(seconds)
        elif outcome == "miss":
            self._aot_misses += 1
            self.m_aot_misses.inc()
        if self.flightrec is not None:
            self.flightrec.record(
                "aot", outcome=outcome, seconds=round(seconds, 6))

    def observe_scalars(self, host_values: Dict[str, float]) -> None:
        """Per-consumed-step scalar taps from the train step's host fetch
        (loss, lr, loss scale)."""
        loss = host_values.get("loss")
        if loss is not None:
            value = float(loss)
            if math.isfinite(value):
                self.m_loss.set(value)
        lr = host_values.get("lr")
        if lr is not None:
            self.m_lr.set(float(lr))
        for key, series in self.m_moe.items():
            if key in host_values:
                series.observe(float(host_values[key]))
        scale = host_values.get("loss_scale")
        if scale is not None:
            value = float(scale)
            self.m_loss_scale.set(value)
            if (
                self._last_loss_scale is not None
                and value != self._last_loss_scale
            ):
                self.m_loss_scale_adjustments.inc()
                if self.flightrec is not None:
                    self.flightrec.record(
                        "loss_scale", scale=value,
                        previous=self._last_loss_scale,
                    )
            self._last_loss_scale = value

    # -- checkpoint + scrape-time feeds ----------------------------------------

    def observe_checkpoint_save(self, seconds: float) -> None:
        self.m_ckpt_save.observe(seconds)
        if self.goodput is not None:
            self.goodput.note_checkpoint("save", seconds)
        if self.flightrec is not None:
            self.flightrec.record(
                "checkpoint_save", seconds=round(seconds, 6))

    def observe_checkpoint_snapshot(self, seconds: float) -> None:
        """Blocking leg of an ASYNC save (device->host snapshot + the
        wait for any previous persist): this IS the save's critical-path
        cost, so it feeds the same save histogram and checkpoint_save
        badput the sync path does — the async win shows up as this number
        shrinking while the persist time moves to the overlapped feed."""
        self.m_ckpt_save.observe(seconds)
        if self.goodput is not None:
            self.goodput.note_checkpoint("save", seconds)
        if self.flightrec is not None:
            self.flightrec.record(
                "ckpt_snapshot", seconds=round(seconds, 6))

    def observe_checkpoint_persist(self, seconds: float,
                                   stalled_s: float = 0.0) -> None:
        """Background leg of an async save (serialize + write, called
        from the persist thread on completion). Only the share that ran
        while training proceeded is ledgered as checkpoint_overlapped_s:
        ``stalled_s`` — time the main thread spent blocked waiting on
        this persist (the next save's barrier, a restore, exit) — is
        already on the critical path and booking it as overlap would
        overstate the async win by exactly the stall."""
        self.m_ckpt_persist.observe(seconds)
        if self.goodput is not None:
            self.goodput.note_checkpoint(
                "save", max(0.0, seconds - stalled_s), overlapped=True
            )
        if self.flightrec is not None:
            self.flightrec.record(
                "ckpt_persist", seconds=round(seconds, 6),
                stalled_s=round(stalled_s, 6))

    def observe_zero1_buckets(self, buckets) -> None:
        """Record the bucketed ZeRO-1 overlap plan (a list of
        ``GradBucket``): the bucket count rides /metrics and the per-
        bucket byte layout lands in the flight recorder, so a post-mortem
        can attribute a collective stall to its bucket."""
        buckets = list(buckets or [])
        self.m_zero1_buckets.set(float(len(buckets)))
        if self.flightrec is not None and buckets:
            self.flightrec.record(
                "zero1_bucket_plan",
                buckets=len(buckets),
                leaf_ranges=[[int(b.lo), int(b.hi)] for b in buckets],
                bucket_bytes=[int(b.nbytes) for b in buckets],
            )

    def observe_grad_exchange(self, per_step: int, *, mesh,
                              micro_batches: int) -> None:
        """Record which step body the trainer built: how often a step's
        gradients cross the mesh (the gauge), and on which mesh over how
        many micro-batches (a ``grad_exchange`` flight-recorder event)."""
        self.m_grad_exchanges.set(float(per_step))
        if self.flightrec is not None:
            self.flightrec.record(
                "grad_exchange", per_step=int(per_step), mesh=mesh,
                micro_batches=int(micro_batches))

    def observe_checkpoint_restore(self, seconds: float) -> None:
        self.m_ckpt_restore.observe(seconds)
        if self.goodput is not None:
            self.goodput.note_checkpoint("restore", seconds)
        if self.flightrec is not None:
            self.flightrec.record(
                "checkpoint_restore", seconds=round(seconds, 6))

    def observe_eval(self, seconds: float) -> None:
        """One eval epoch's wall time — badput under the goodput
        discipline (chips busy, no training progress)."""
        if self.goodput is not None:
            self.goodput.note_eval(seconds)
        if self.flightrec is not None:
            self.flightrec.record("eval", seconds=round(seconds, 6))

    def refresh(self) -> None:
        """Scrape-time gauges: watchdog heartbeat age, goodput accounting
        + supervisor sidecar (registered as the exporter's pre-render
        hook)."""
        age = None
        if self.watchdog is not None:
            age = self.watchdog.heartbeat_age()
        self.m_heartbeat_age.set(age if age is not None else -1.0)

        if self.goodput is not None:
            summary = self.goodput.summary()
            ratio = summary["goodput_ratio"]
            self.m_goodput.set(ratio if ratio is not None else -1.0)
            for category, seconds in summary["badput_s"].items():
                self.m_badput.set(category, seconds)

        if self.supervisor_state_path is None:
            return
        from ..resilience.supervisor import peek_supervisor_state

        state = peek_supervisor_state(self.supervisor_state_path)
        if state is None:
            return
        self.m_sup_restarts.set(float(state.get("restarts_used", 0)))
        self.m_sup_attempts.set(float(state.get("attempts", 0)))
        outcomes = state.get("outcomes", [])
        self.m_sup_preempted.set(float(outcomes.count("preempted")))
        self.m_sup_hang.set(float(outcomes.count("hang")))
        self.m_sup_crash.set(float(outcomes.count("crash")))

    def health_document(self, *, global_step, process_index: int = 0) -> dict:
        """The /healthz JSON body: liveness AND productivity in one probe
        (the serving-fleet router and the supervisor read the same
        document). Goodput ratio and flight-recorder last-event age are
        None when the respective plane is not attached."""
        heartbeat = (
            self.watchdog.heartbeat_age() if self.watchdog is not None
            else None
        )
        doc = {
            "status": "ok",
            "global_step": global_step,
            "process_index": process_index,
            "watchdog_heartbeat_age_s": heartbeat,
            "goodput_ratio": None,
            "last_event_age_s": None,
        }
        if self.goodput is not None:
            doc["goodput_ratio"] = self.goodput.summary()["goodput_ratio"]
        if self.flightrec is not None:
            doc["last_event_age_s"] = self.flightrec.last_event_age()
        return doc

    # -- bench surface ----------------------------------------------------------

    def breakdown_summary(self) -> dict:
        """Step-time breakdown percentiles + anomaly count for the bench
        JSON line (seconds)."""
        def q(hist, p):
            value = hist.quantile(p)
            return round(value, 6) if value is not None else None

        return {
            "step_p50_s": q(self.m_step, 0.5),
            "step_p95_s": q(self.m_step, 0.95),
            "data_wait_p50_s": q(self.m_data_wait, 0.5),
            "data_wait_p95_s": q(self.m_data_wait, 0.95),
            "host_p50_s": q(self.m_host, 0.5),
            "host_p95_s": q(self.m_host, 0.95),
            "device_p50_s": q(self.m_device, 0.5),
            "device_p95_s": q(self.m_device, 0.95),
            "slow_step_anomalies": self.detector.anomalies,
        }

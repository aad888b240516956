"""Unified observability plane tests (metrics/ + train/telemetry.py).

Covers the ISSUE-10 acceptance surface on the CPU mesh: registry-lift
back-compat (serve.metrics is a shim over metrics.registry), the
step-time breakdown accounting (components partition the step wall), the
slow-step anomaly detector (fires on a synthetic stall, quiet on steady
traces), Chrome trace-event JSON validity for BOTH planes' span streams,
the /metrics exporter end-to-end scrape, the supervisor JSON sidecar, the
watchdog heartbeat age, the StepTimer exception-narrowing satellite, and
the off == bit-identical trajectory pin.

ISSUE-13 grows the run-level layer: goodput-ledger accounting exactness
(categories partition wall-clock; recompute loss from a REAL
save→crash→resume cycle under the fault registry through the real
Supervisor), pod-scope aggregation over live host exporters (+ the
/metrics/pod route), flight-recorder dump-on-fault with the supervisor
diagnosis read-back, the /healthz liveness+productivity document, the
trace-merge script, and the time_profiler-on-spans migration.
"""

import json
import logging
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from ml_recipe_tpu.metrics import trace as trace_mod
from ml_recipe_tpu.metrics.anomaly import SlowStepDetector
from ml_recipe_tpu.metrics.aggregator import PodAggregator, parse_prometheus_text
from ml_recipe_tpu.metrics.exporter import MetricsExporter
from ml_recipe_tpu.metrics.flightrec import (
    FlightRecorder,
    newest_flight_record,
    timeline_lines,
)
from ml_recipe_tpu.metrics.goodput import (
    BADPUT_CATEGORIES,
    GOODPUT_FILENAME,
    GoodputLedger,
    read_ledger,
    summarize_events,
)
from ml_recipe_tpu.metrics.registry import Registry
from ml_recipe_tpu.metrics.trace import TraceWriter
from ml_recipe_tpu.train.telemetry import TrainTelemetry

from helpers import make_tokenizer
from test_trainer import _make_trainer, _param_snapshot

_REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def tracer(tmp_path):
    """Install a process-global TraceWriter; always uninstall after."""
    writer = trace_mod.install(
        TraceWriter(str(tmp_path / "trace.json"), process_name="test"))
    try:
        yield writer
    finally:
        trace_mod.install(None)


def _validate_chrome_trace(path):
    """Assert the file parses as Chrome trace-event JSON and return the
    events (the schema Perfetto's importer requires: traceEvents list,
    every event carrying name/ph/ts/pid/tid; complete events a dur)."""
    with open(path) as fh:
        doc = json.load(fh)
    assert isinstance(doc, dict)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for event in events:
        assert isinstance(event["name"], str) and event["name"]
        assert event["ph"] in ("X", "i")
        assert isinstance(event["ts"], (int, float)) and event["ts"] >= 0
        assert isinstance(event["pid"], int)
        assert isinstance(event["tid"], int)
        if event["ph"] == "X":
            assert isinstance(event["dur"], (int, float))
            assert event["dur"] >= 0
    return events


# ---------------------------------------------------------------------------
# registry lift
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_registry_lift_backcompat():
    """serve.metrics must remain a faithful shim: same classes (not
    copies), so isinstance checks and registries interoperate across both
    planes."""
    from ml_recipe_tpu import metrics as metrics_pkg
    from ml_recipe_tpu.metrics import registry as shared
    from ml_recipe_tpu.serve import metrics as shim

    for name in ("Counter", "Gauge", "Histogram", "Info", "Registry"):
        assert getattr(shim, name) is getattr(shared, name), name
        assert getattr(metrics_pkg, name) is getattr(shared, name), name
    assert shim.DEFAULT_BUCKETS == shared.DEFAULT_BUCKETS

    # the serve package surface (serve/__init__.py) still resolves
    from ml_recipe_tpu.serve import Counter, Registry as ServeRegistry

    assert ServeRegistry is shared.Registry
    assert Counter is shared.Counter


# ---------------------------------------------------------------------------
# trace writer
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_trace_writer_chrome_schema(tmp_path):
    writer = TraceWriter(str(tmp_path / "t.json"))
    with writer.span("outer", cat="test", args={"k": 1}):
        with writer.span("inner", cat="test"):
            pass
    t0 = writer.now()
    writer.complete("explicit", t0, t0 + 0.001, cat="test",
                    args={"request_id": 7})
    writer.instant("marker", cat="test")
    path = writer.close()
    events = _validate_chrome_trace(path)
    names = [e["name"] for e in events]
    assert set(names) == {"outer", "inner", "explicit", "marker"}
    explicit = next(e for e in events if e["name"] == "explicit")
    assert explicit["args"]["request_id"] == 7
    assert abs(explicit["dur"] - 1000.0) < 1.0  # 1 ms in microseconds


@pytest.mark.unit
def test_trace_module_noops_without_tracer():
    assert trace_mod.current() is None
    with trace_mod.span("nothing"):
        pass
    trace_mod.complete("nothing", 0.0, 1.0)
    # none of these may raise; span and complete go to the always-on record
    # all the same (tests/test_span_plane.py), an instant nowhere
    trace_mod.instant("nothing")


@pytest.mark.unit
def test_trace_writer_bounds_memory(tmp_path):
    writer = TraceWriter(str(tmp_path / "b.json"))
    for i in range(trace_mod._MAX_EVENTS + 10):
        writer.complete("e", 0.0, 0.0)
    assert len(writer) <= trace_mod._MAX_EVENTS
    with open(writer.flush()) as fh:
        assert json.load(fh)["otherData"]["dropped_events"] > 0


# ---------------------------------------------------------------------------
# anomaly detector
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_anomaly_detector_quiet_on_steady_trace():
    det = SlowStepDetector(factor=3.0, window=64, min_steps=8)
    rng = np.random.default_rng(0)
    for i in range(200):  # ±5% jitter around 100 ms: healthy steady state
        t = 0.1 * (1.0 + 0.05 * float(rng.uniform(-1, 1)))
        assert det.update(i, t, {"data_wait": 0.01, "host": 0.02,
                                 "device": t - 0.03}) is None
    assert det.anomalies == 0


@pytest.mark.unit
def test_anomaly_detector_fires_on_stall_with_attribution():
    det = SlowStepDetector(factor=3.0, window=64, min_steps=8)
    for i in range(32):
        det.update(i, 0.1, {"data_wait": 0.01, "host": 0.02, "device": 0.07})
    # injected loader stall: data_wait explodes, device unchanged
    report = det.update(
        32, 0.5, {"data_wait": 0.41, "host": 0.02, "device": 0.07})
    assert report is not None
    assert report.attribution == "data_wait"
    assert report.step == 32
    assert report.total_s == pytest.approx(0.5)
    assert report.threshold_s <= 0.5
    assert "SLOW STEP 32" in report.message()
    assert det.anomalies == 1


@pytest.mark.unit
def test_anomaly_detector_warmup_and_min_window():
    det = SlowStepDetector(factor=3.0, window=8, warmup=1, min_steps=8)
    # the first (compiling) step is 100x steady state: warmup skips it
    assert det.update(0, 10.0) is None
    # fewer than min_steps in the window: never fires, whatever the value
    for i in range(1, 8):
        assert det.update(i, 50.0 if i == 5 else 0.1) is None


# ---------------------------------------------------------------------------
# telemetry accounting + exporter
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_breakdown_components_sum_to_total():
    tele = TrainTelemetry()
    rng = np.random.default_rng(1)
    expect_total = 0.0
    for i in range(32):
        dw, h, dev = rng.uniform(0.001, 0.05, size=3)
        expect_total += dw + h + dev
        tele.observe_step(i, data_wait_s=dw, host_s=h, device_s=dev,
                          examples=16, real_tokens=500, total_tokens=512)
    assert tele.m_step.count == 32
    parts = (tele.m_data_wait.sum + tele.m_host.sum + tele.m_device.sum)
    assert tele.m_step.sum == pytest.approx(parts, rel=1e-9)
    assert tele.m_step.sum == pytest.approx(expect_total, rel=1e-9)
    assert tele.m_padding_waste.value == pytest.approx(
        100.0 * (1.0 - 500 / 512))
    summary = tele.breakdown_summary()
    assert summary["slow_step_anomalies"] == 0
    assert summary["step_p50_s"] > 0
    assert summary["device_p95_s"] > 0


@pytest.mark.unit
def test_loss_scale_adjustment_counting():
    tele = TrainTelemetry()
    for scale in (32768.0, 32768.0, 16384.0, 16384.0, 32768.0):
        tele.observe_scalars({"loss": 1.0, "lr": 1e-4, "loss_scale": scale})
    assert tele.m_loss_scale_adjustments.value == 2  # halve + re-double
    assert tele.m_loss_scale.value == 32768.0


def test_exporter_e2e_scrape(tmp_path):
    """A live scrape sees every registered training metric, /healthz
    answers, and pre-render hooks run before the render (the supervisor
    sidecar counts update per scrape)."""
    from ml_recipe_tpu.resilience.supervisor import write_supervisor_state

    sidecar = tmp_path / "supervisor_state.json"
    write_supervisor_state(sidecar, {
        "attempts": 3, "restarts_used": 2,
        "outcomes": ["crash", "preempted", "hang"],
    })
    tele = TrainTelemetry(supervisor_state_path=sidecar)
    tele.observe_step(5, data_wait_s=0.01, host_s=0.02, device_s=0.1,
                      examples=8, real_tokens=100, total_tokens=128)
    exporter = MetricsExporter(
        tele.registry, port=0, host="127.0.0.1",
        health_fn=lambda: {"status": "ok", "global_step": 5},
    ).start()
    exporter.add_pre_render(tele.refresh)
    try:
        url = f"http://127.0.0.1:{exporter.port}"
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            text = r.read().decode()
        for name in tele.registry.names():
            assert name in text, name
        # sidecar counts arrived through the pre-render hook
        assert "train_supervisor_restarts 2" in text
        assert "train_supervisor_attempts 3" in text
        assert "train_supervisor_exits_hang 1" in text
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health == {"status": "ok", "global_step": 5}
    finally:
        exporter.close()


# ---------------------------------------------------------------------------
# supervisor sidecar
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_supervisor_persists_observable_state(tmp_path):
    from ml_recipe_tpu.resilience.supervisor import (
        PREEMPT_EXIT_CODE,
        RetryPolicy,
        Supervisor,
        peek_supervisor_state,
    )

    sidecar = tmp_path / "supervisor_state.json"
    steps = iter([None, 10, 10, 20])  # before/after attempt 1, 2
    codes = iter([PREEMPT_EXIT_CODE, 0])
    seen = []

    def launch(i):
        # the sidecar must already exist (status=running) when the child —
        # whose exporter reads it — comes up
        seen.append(peek_supervisor_state(sidecar))
        return next(codes)

    result = Supervisor(
        launch,
        progress=lambda: next(steps),
        policy=RetryPolicy(max_restarts=3, backoff_base=0.0),
        sleep=lambda s: None,
        state_path=sidecar,
    ).run()
    assert result.status == "clean"
    assert seen[0]["status"] == "running" and seen[0]["attempts"] == 0
    assert seen[1]["attempts"] == 1
    assert seen[1]["outcomes"] == ["preempted"]

    final = peek_supervisor_state(sidecar)
    assert final["status"] == "clean"
    assert final["attempts"] == 2
    assert final["outcomes"] == ["preempted", "clean"]
    assert final["restarts_used"] == 0  # the preemption made progress
    assert final["step"] == 20
    assert "updated_at" in final


@pytest.mark.unit
def test_peek_supervisor_state_tolerates_garbage(tmp_path):
    from ml_recipe_tpu.resilience.supervisor import peek_supervisor_state

    assert peek_supervisor_state(tmp_path / "missing.json") is None
    bad = tmp_path / "bad.json"
    bad.write_text("{ torn writ")
    assert peek_supervisor_state(bad) is None
    notdict = tmp_path / "list.json"
    notdict.write_text("[1, 2]")
    assert peek_supervisor_state(notdict) is None


# ---------------------------------------------------------------------------
# watchdog heartbeat
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_watchdog_heartbeat_age():
    from ml_recipe_tpu.resilience.watchdog import Watchdog

    wd = Watchdog(timeout=30.0)
    try:
        assert wd.heartbeat_age() is None  # nothing armed yet
        with wd.watch("step frame") as tick:
            assert wd.heartbeat_age() < 1.0
            tick("step 1")
            assert wd.heartbeat_age() < 1.0
        wd.note_progress(1)
        assert wd.heartbeat_age() < 1.0
    finally:
        wd.stop()


# ---------------------------------------------------------------------------
# StepTimer satellite: only ImportError is survivable
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_steptimer_propagates_non_import_errors(monkeypatch):
    from ml_recipe_tpu.utils import profiler

    class _BrokenJax:
        @staticmethod
        def block_until_ready(result):
            raise ValueError("typo'd result tree")

    monkeypatch.setitem(__import__("sys").modules, "jax", _BrokenJax())
    timer = profiler.StepTimer()
    timer.start()
    with pytest.raises(ValueError, match="typo'd result tree"):
        timer.stop(object())


@pytest.mark.unit
def test_steptimer_warns_once_without_jax(monkeypatch, caplog):
    import sys

    from ml_recipe_tpu.utils import profiler

    # sys.modules[name] = None makes `import jax` raise ImportError
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setattr(profiler.StepTimer, "_warned_no_jax", False)
    timer = profiler.StepTimer()
    with caplog.at_level(logging.WARNING, logger="ml_recipe_tpu.utils.profiler"):
        for _ in range(3):
            timer.start()
            timer.stop(object())
    warnings = [r for r in caplog.records if "dispatch only" in r.message]
    assert len(warnings) == 1  # warn once, then stay quiet


# ---------------------------------------------------------------------------
# trainer end to end: breakdown + spans + off == bit-identical
# ---------------------------------------------------------------------------


def test_trainer_breakdown_and_trace_spans(tmp_path, tracer):
    """Instrumented tiny run: the telemetry surface fills with exactly one
    observation per step, components partition the step wall, checkpoint
    timings land, and the span stream is valid Chrome trace JSON covering
    the training step window."""
    tele = TrainTelemetry(anomaly_window=16)
    trainer, _ = _make_trainer(
        tmp_path, dropout=0.0, telemetry=tele, device_prefetch=0)
    trainer.train()
    steps = trainer.global_step
    assert steps == 2  # train_len 32 / global batch 16

    assert tele.m_steps.value == steps
    assert tele.m_step.count == steps
    assert tele.m_data_wait.count == steps
    assert tele.m_host.count == steps
    assert tele.m_device.count == steps
    assert tele.m_step.sum == pytest.approx(
        tele.m_data_wait.sum + tele.m_host.sum + tele.m_device.sum,
        rel=1e-9,
    )
    assert tele.m_device.sum > 0  # the block-until-ready leg is real time
    assert tele.m_global_step.value == steps - 1  # last observed step id
    assert tele.m_lr.value > 0  # scalars tapped from the host fetch
    # attention_mask accounting flowed through the place() wrapper
    assert tele.m_tokens_per_sec.value > 0
    assert 0.0 <= tele.m_padding_waste.value <= 100.0

    trainer.save_state_dict(tmp_path / "obs.ch")
    trainer.load_state_dict(tmp_path / "obs.ch")
    assert tele.m_ckpt_save.count == 1
    assert tele.m_ckpt_restore.count == 1

    events = _validate_chrome_trace(tracer.close())
    names = {e["name"] for e in events}
    assert {"data_wait", "place", "step", "checkpoint_save",
            "checkpoint_restore"} <= names
    step_events = [e for e in events if e["name"] == "step"]
    assert len(step_events) == steps
    assert {e["args"]["step"] for e in step_events} == set(range(steps))
    # the legacy time_profiler decorator now rides the span plane: the
    # epoch-level `_train` wall time appears as a cat="profile" span
    profile = [e for e in events if e["name"] == "_train"]
    assert profile and all(e["cat"] == "profile" for e in profile)


def test_trainer_prefetch_instrumentation(tmp_path):
    """With the prefetch thread on, host placement stats still arrive
    (FIFO-matched across the queue) but are EXCLUDED from the step-wall
    total: placement overlaps the previous step's device compute, so
    counting it would overstate the wall (a prefetch thread falling
    behind surfaces as data wait instead)."""
    tele = TrainTelemetry()
    trainer, _ = _make_trainer(
        tmp_path, dropout=0.0, telemetry=tele, device_prefetch=2)
    trainer.train()
    assert tele.m_steps.value == trainer.global_step == 2
    assert tele.m_host.count == 2
    assert tele.m_host.sum > 0  # recorded on the prefetch thread
    # total = data_wait + device only (host overlapped); note the first
    # (preflight) step runs inline before the prefetcher exists, so its
    # host leg IS on the wall and in the total
    assert tele.m_step.sum < (
        tele.m_data_wait.sum + tele.m_host.sum + tele.m_device.sum)
    assert tele.m_step.sum >= tele.m_data_wait.sum + tele.m_device.sum


def test_observability_off_is_bit_identical(tmp_path):
    """Acceptance pin: the instrumented trajectory (telemetry + tracer,
    blocking per step) equals the untouched off-path trajectory bit for
    bit — observability must never perturb training arithmetic."""
    (tmp_path / "off").mkdir()
    (tmp_path / "on").mkdir()
    t_off, _ = _make_trainer(tmp_path / "off", dropout=0.1)
    t_off.train()
    base = _param_snapshot(t_off.params)

    tracer = trace_mod.install(
        TraceWriter(str(tmp_path / "on" / "trace.json")))
    try:
        # the FULL instrumented stack, run-level layer included: goodput
        # ledger + flight recorder feed from the same step loop and must
        # also never perturb the arithmetic
        tele = TrainTelemetry(
            goodput=GoodputLedger(
                str(tmp_path / "on" / "goodput.jsonl"), flush_every=1),
            flightrec=FlightRecorder(
                str(tmp_path / "on" / "flightrec_p0.json"), flush_every=1),
        )
        t_on, _ = _make_trainer(tmp_path / "on", dropout=0.1, telemetry=tele)
        t_on.train()
    finally:
        trace_mod.install(None)
        tracer.close()
    instrumented = _param_snapshot(t_on.params)
    # the run-level artifacts actually materialized while staying inert
    assert read_ledger(tmp_path / "on" / "goodput.jsonl")
    assert newest_flight_record(tmp_path / "on") is not None

    flat_a, _ = jax.tree_util.tree_flatten(base)
    flat_b, _ = jax.tree_util.tree_flatten(instrumented)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# serving plane: request-lifecycle spans
# ---------------------------------------------------------------------------


def test_serving_request_lifecycle_spans(tmp_path, tracer):
    """One request through engine + HTTP front end leaves the full span
    chain — admission, queue, flush, device, span_reduce, respond — keyed
    by its request id, in valid Chrome trace JSON."""
    from ml_recipe_tpu.models import EncoderConfig, QAModel
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.serve.bucketing import BucketGrid
    from ml_recipe_tpu.serve.engine import QAEngine
    from ml_recipe_tpu.serve.server import QAServer

    tok = make_tokenizer(tmp_path)
    cfg = EncoderConfig(
        vocab_size=len(tok), hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, max_position_embeddings=66, num_labels=5,
    )
    model = QAModel(cfg)
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), dtype=np.int32))["params"]
    engine = QAEngine(
        model, params, tok,
        grid=BucketGrid.from_spec("4x64"),
        mesh=build_mesh(),
        max_batch_delay_ms=5,
        queue_size=16,
        max_question_len=16,
        doc_stride=24,
    )
    engine.warmup(hbm_preflight=False)
    server = QAServer(engine, port=0, request_timeout_s=60)
    server.start()
    try:
        body = json.dumps({
            "question": "what is the capital of england ?",
            "document": "<P> London is the capital of England . </P>",
        }).encode()
        req = urllib.request.Request(
            f"http://{server.host}:{server.port}/v1/qa", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
    finally:
        server.stop()
        server.shutdown()

    events = _validate_chrome_trace(tracer.close())
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("admission", "queue", "flush", "device", "span_reduce",
                 "respond"):
        assert name in by_name, name
    rid = by_name["admission"][-1]["args"]["request_id"]
    assert any(e["args"]["request_id"] == rid for e in by_name["queue"])
    assert any(e["args"]["request_id"] == rid
               for e in by_name["span_reduce"])
    assert any(e["args"]["request_id"] == rid for e in by_name["respond"])
    assert all(e["cat"] == "serve" for e in by_name["device"])


# ---------------------------------------------------------------------------
# goodput ledger: accounting exactness
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_goodput_partition_is_exact():
    """The summarizer's categories + productive time partition total
    wall-clock EXACTLY (`other` is the explicit residual), restart
    downtime comes from attempt boundaries, and a resume reclassifies
    replayed step time as recompute — all on hand-computed events."""
    events = [
        {"ev": "attempt_start", "t": 0.0, "attempt": 0, "resume_step": None},
        {"ev": "run_start", "t": 1.0, "step": 0},
        {"ev": "steps", "t": 5.0, "first_step": 0, "last_step": 3,
         "steps": 4, "productive_s": 3.0, "data_wait_s": 0.5,
         "compile_s": 0.5},
        {"ev": "checkpoint", "t": 6.0, "kind": "save", "seconds": 1.0},
        {"ev": "attempt_end", "t": 7.0, "attempt": 0, "returncode": 89,
         "outcome": "crash", "step": 2},
        {"ev": "attempt_start", "t": 9.0, "attempt": 1, "resume_step": 2},
        {"ev": "run_start", "t": 10.0, "step": 2},
        {"ev": "steps", "t": 14.0, "first_step": 2, "last_step": 5,
         "steps": 4, "productive_s": 4.0, "data_wait_s": 0.0,
         "compile_s": 0.0},
        {"ev": "eval", "t": 15.0, "seconds": 0.5},
        {"ev": "run_end", "t": 16.0, "step": 6},
    ]
    s = summarize_events(events)
    assert s["total_wall_s"] == pytest.approx(16.0)
    # resume at step 2: the first window's steps 2..3 (2 of 4) replayed
    assert s["recomputed_steps"] == 2
    assert s["badput_s"]["recompute"] == pytest.approx(1.5)
    assert s["productive_s"] == pytest.approx(3.0 - 1.5 + 4.0)
    assert s["badput_s"]["restart_downtime"] == pytest.approx(2.0)
    assert s["badput_s"]["compile_warmup"] == pytest.approx(0.5)
    assert s["badput_s"]["data_wait"] == pytest.approx(0.5)
    assert s["badput_s"]["checkpoint_save"] == pytest.approx(1.0)
    assert s["badput_s"]["eval"] == pytest.approx(0.5)
    assert s["attempts"] == 2
    # the acceptance bound (1%) and the construction guarantee (exact)
    parts = s["productive_s"] + sum(s["badput_s"].values())
    assert parts == pytest.approx(s["total_wall_s"], rel=1e-9)
    assert set(s["badput_s"]) == set(BADPUT_CATEGORIES)
    assert 0.0 < s["goodput_ratio"] < 1.0


@pytest.mark.unit
def test_goodput_checkpoint_overlapped_split():
    """ISSUE-14: the blocking-vs-overlapped checkpoint split. An async
    save's background persist (``overlapped: true`` checkpoint events)
    accumulates into ``checkpoint_overlapped_s`` OUTSIDE the badput
    partition — it ran CONCURRENTLY with productive steps, so booking it
    as badput would double-count wall-clock. The partition stays exact
    and checkpoint_save badput carries the blocking share only."""
    events = [
        {"ev": "run_start", "t": 0.0, "step": 0},
        {"ev": "steps", "t": 4.0, "first_step": 0, "last_step": 3,
         "steps": 4, "productive_s": 3.5, "data_wait_s": 0.0,
         "compile_s": 0.0},
        # blocking snapshot (critical path) + overlapped persist (under
        # the next steps' device time)
        {"ev": "checkpoint", "t": 4.1, "kind": "save", "seconds": 0.1},
        {"ev": "checkpoint", "t": 5.0, "kind": "save", "seconds": 0.8,
         "overlapped": True},
        {"ev": "run_end", "t": 5.0, "step": 4},
    ]
    s = summarize_events(events)
    assert s["badput_s"]["checkpoint_save"] == pytest.approx(0.1)
    assert s["checkpoint_overlapped_s"] == pytest.approx(0.8)
    # exactness holds WITHOUT the overlapped share: the 0.8s ran under
    # the productive window, not on its own wall-clock
    parts = s["productive_s"] + sum(s["badput_s"].values())
    assert parts == pytest.approx(s["total_wall_s"], rel=1e-9)
    assert set(s["badput_s"]) == set(BADPUT_CATEGORIES)

    # writer side: note_checkpoint(overlapped=True) emits the marked event
    ledger = GoodputLedger(None)
    ledger.note_checkpoint("save", 0.05)
    ledger.note_checkpoint("save", 0.5, overlapped=True)
    s2 = ledger.summary()
    assert s2["badput_s"]["checkpoint_save"] == pytest.approx(0.05)
    assert s2["checkpoint_overlapped_s"] == pytest.approx(0.5)
    assert "overlapped" in ledger.summary_message()


@pytest.mark.unit
def test_telemetry_async_checkpoint_observers(tmp_path):
    """observe_checkpoint_snapshot feeds the save histogram + blocking
    badput (it IS the critical-path save cost); observe_checkpoint_persist
    feeds the persist histogram + the overlapped ledger field; both land
    as ckpt_snapshot / ckpt_persist flight-recorder events."""
    ledger = GoodputLedger(None)
    rec = FlightRecorder(str(tmp_path / "flightrec_p0.json"), flush_every=64)
    tele = TrainTelemetry(goodput=ledger, flightrec=rec)
    ledger.note_run_start(0)
    tele.observe_checkpoint_snapshot(0.02)
    tele.observe_checkpoint_persist(0.4)

    s = ledger.summary()
    assert s["badput_s"]["checkpoint_save"] == pytest.approx(0.02)
    assert s["checkpoint_overlapped_s"] == pytest.approx(0.4)

    out = tele.registry.render()
    assert "train_checkpoint_persist_seconds" in out

    rec.dump("test")
    _, doc = newest_flight_record(tmp_path)
    kinds = [e["kind"] for e in doc["events"]]
    assert "ckpt_snapshot" in kinds and "ckpt_persist" in kinds


@pytest.mark.unit
def test_telemetry_grad_exchange_observer(tmp_path):
    """Which step body the trainer built: exchanges a step on the gauge,
    mesh and micro-batch count as a ``grad_exchange`` flight-recorder
    event."""
    rec = FlightRecorder(str(tmp_path / "flightrec_p0.json"), flush_every=64)
    tele = TrainTelemetry(flightrec=rec)
    tele.observe_grad_exchange(1, mesh="data:4", micro_batches=8)
    assert "train_grad_exchanges_per_step 1" in tele.registry.render()
    rec.dump("test")
    _, doc = newest_flight_record(tmp_path)
    event = next(e for e in doc["events"] if e["kind"] == "grad_exchange")
    assert (event["per_step"], event["mesh"], event["micro_batches"]) == (
        1, "data:4", 8)


@pytest.mark.unit
def test_trainer_reports_its_gradient_exchange(tmp_path, caplog):
    """The trainer logs the body it built once, and tells the telemetry."""
    import logging

    from test_trainer import _make_trainer

    tele = TrainTelemetry()
    for mesh_spec, split, prng, want, text in (
            ("data:4", 2, "rbg", 1, "once a step (data:4, 2 micro-batch"),
            ("data:4", 2, "threefry2x32", 2, "every micro-batch (data:4"),
            ("data:1", 2, "rbg", 0, None)):
        trainer, _ = _make_trainer(tmp_path, mesh_spec=mesh_spec,
                                   batch_split=split, prng_impl=prng,
                                   telemetry=tele)
        caplog.clear()
        with caplog.at_level(logging.INFO, "ml_recipe_tpu.train.trainer"):
            trainer._build_train_step()
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("gradient exchange:")]
        assert len(lines) == (text is not None)
        assert text is None or text in lines[0]
        assert trainer.grad_exchanges_per_step == want
        assert f"train_grad_exchanges_per_step {want}" in \
            tele.registry.render()


def test_trainer_reports_its_causal_backward_once(tmp_path, caplog,
                                                  monkeypatch):
    """Which backward the causal kernels got is in the step program's
    instruction names: the trainer logs the count once, when the program's
    scope map is first read, and says nothing for a program without them."""
    import logging

    from ml_recipe_tpu.metrics import trace
    from ml_recipe_tpu.train import Trainer
    from test_trainer import _make_trainer

    call = ('  %{name} = f32[8]{{0}} custom-call(%Arg_0.1), custom_call_target='
            '"tpu_custom_call", metadata={{op_name="jit(train_step)/while/'
            'body/transpose(jvp(M))/layer_{i}/attention/flash_bwd/x"}}')
    for names, text in (
            ([f"flash_causal_bwd.{i}" for i in range(5)],
             "5 fused, 0 split call(s) in jit_train_step"),
            (["flash_causal_bwd_dq.1", "flash_causal_bwd_dkv.2"],
             "0 fused, 1 split call(s) in jit_train_step"),
            (["custom-call.7"], None)):
        body = "\n".join(call.format(name=name, i=i)
                         for i, name in enumerate(names))
        hlo = ("HloModule jit_train_step\n\nENTRY %main.1 (Arg_0.1: f32[8]) "
               "-> f32[8] {\n  %Arg_0.1 = f32[8]{0} parameter(0)\n"
               + body + "\n}\n")
        monkeypatch.setattr(trace, "_programs", {})
        monkeypatch.setattr(trace, "_scope_maps", {})
        monkeypatch.setattr(Trainer, "_train_step_hlo_text",
                            lambda self, hlo=hlo: hlo)
        trainer, _ = _make_trainer(tmp_path)
        trainer._build_train_step()
        caplog.clear()
        with caplog.at_level(logging.INFO, "ml_recipe_tpu.train.trainer"):
            assert len(trace.scope_map("jit_train_step")) == len(names)
            trace.scope_map("jit_train_step")
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("causal_backward:")]
        assert len(lines) == (text is not None)
        assert text is None or lines[0].endswith(text)


@pytest.mark.unit
def test_goodput_crash_loop_resumes_reclassify_once():
    """A crash loop resuming repeatedly from the SAME checkpoint must
    reclassify each window's replayed tail exactly once — not pro-rate
    the already-moved share again on every restart (which would decay
    reported goodput geometrically on the runs the ledger exists for)."""
    window = {"ev": "steps", "t": 1.0, "first_step": 0, "last_step": 99,
              "steps": 100, "productive_s": 100.0}
    resumes = [
        {"ev": "run_start", "t": 2.0, "step": 50},
        {"ev": "run_start", "t": 3.0, "step": 50},
        {"ev": "run_start", "t": 4.0, "step": 50},
    ]
    s = summarize_events([window] + resumes)
    assert s["badput_s"]["recompute"] == pytest.approx(50.0)
    assert s["productive_s"] == pytest.approx(50.0)
    assert s["recomputed_steps"] == 50


@pytest.mark.unit
def test_goodput_summarizer_edge_cases():
    assert summarize_events([])["goodput_ratio"] is None
    # stampless / unknown events are ignored, not fatal
    s = summarize_events([{"ev": "steps"}, {"ev": "mystery", "t": 1.0}])
    assert s["steps"] == 0
    # live read: `now` extends the window beyond the last event
    s = summarize_events(
        [{"ev": "steps", "t": 0.0, "first_step": 0, "last_step": 0,
          "steps": 1, "productive_s": 1.0}],
        now=4.0,
    )
    assert s["total_wall_s"] == pytest.approx(4.0)
    assert s["goodput_ratio"] == pytest.approx(0.25)


@pytest.mark.unit
def test_goodput_ledger_persists_and_reads_prior_attempts(tmp_path):
    """The ledger file survives the writer: a second ledger (a resumed
    attempt) reads the first attempt's events into its own accounting,
    and windows flush durably every `flush_every` steps."""
    path = tmp_path / GOODPUT_FILENAME
    first = GoodputLedger(path, flush_every=2)
    first.note_run_start(0)
    first.note_step(0, wall_s=1.0, data_wait_s=0.25, compile=True)
    first.note_step(1, wall_s=0.5, data_wait_s=0.1)   # window flushes here
    first.note_step(2, wall_s=0.5)                    # open window: NOT on disk
    on_disk = read_ledger(path)
    assert [e["ev"] for e in on_disk] == ["run_start", "steps"]
    # ...but the live summary still sees the open window
    assert first.summary()["steps"] == 3

    resumed = GoodputLedger(path, flush_every=2)
    resumed.note_run_start(1)  # resume at step 1: step 1 gets replayed
    resumed.note_step(1, wall_s=0.4)
    resumed.note_run_end(2)
    s = resumed.summary()
    assert s["recomputed_steps"] == 1
    # the flushed window held steps 0-1 with 0.4s productive (step 0's
    # share went to compile); the replayed half is pro-rated out
    assert s["badput_s"]["recompute"] == pytest.approx(0.2, abs=1e-6)
    assert s["badput_s"]["compile_warmup"] == pytest.approx(0.75)
    # synthetic durations exceed the real wall window here, so the
    # residual clamps at zero (the exact-partition property is pinned on
    # hand-stamped events in test_goodput_partition_is_exact)
    assert s["badput_s"]["other"] == 0.0
    assert "GOODPUT: ratio" in resumed.summary_message()


@pytest.mark.unit
def test_labeled_gauge_renders_per_category():
    reg = Registry()
    g = reg.labeled_gauge("train_badput_seconds_total", "badput", "category")
    g.set("data_wait", 1.5)
    g.inc("recompute", 2.0)
    out = reg.render()
    assert 'train_badput_seconds_total{category="data_wait"} 1.5' in out
    assert 'train_badput_seconds_total{category="recompute"} 2' in out
    assert g.values() == {"data_wait": 1.5, "recompute": 2.0}


def test_telemetry_feeds_ledger_and_recorder(tmp_path):
    """The telemetry plane is the feed point: first step books
    compile/warmup, checkpoints and eval land in the ledger, the anomaly
    verdict lands in the flight recorder (attribution survives the crash
    that follows a stall), and refresh() exports the goodput gauges."""
    ledger = GoodputLedger(tmp_path / GOODPUT_FILENAME, flush_every=4)
    rec = FlightRecorder(str(tmp_path / "flightrec_p0.json"), flush_every=64)
    tele = TrainTelemetry(
        anomaly_min_steps=8, goodput=ledger, flightrec=rec)
    ledger.note_run_start(0)
    for i in range(32):
        tele.observe_step(i, data_wait_s=0.01, host_s=0.02, device_s=0.07)
    # injected stall: the detector fires and the verdict is recorded
    report = tele.observe_step(
        32, data_wait_s=0.41, host_s=0.02, device_s=0.07)
    assert report is not None and report.attribution == "data_wait"
    tele.observe_checkpoint_save(0.2)
    tele.observe_checkpoint_restore(0.1)
    tele.observe_eval(0.3)
    tele.observe_scalars({"loss_scale": 32768.0})
    tele.observe_scalars({"loss_scale": 16384.0})

    s = ledger.summary()
    assert s["steps"] == 33
    assert s["badput_s"]["compile_warmup"] > 0   # step 0 booked as compile
    assert s["badput_s"]["checkpoint_save"] == pytest.approx(0.2)
    assert s["badput_s"]["checkpoint_restore"] == pytest.approx(0.1)
    assert s["badput_s"]["eval"] == pytest.approx(0.3)

    rec.dump("test")
    path_doc = newest_flight_record(tmp_path)
    assert path_doc is not None
    _, doc = path_doc
    kinds = [e["kind"] for e in doc["events"]]
    assert "slow_step" in kinds and "checkpoint_save" in kinds
    assert "eval" in kinds and "loss_scale" in kinds
    slow = next(e for e in doc["events"] if e["kind"] == "slow_step")
    assert slow["attribution"] == "data_wait" and slow["step"] == 32

    tele.refresh()
    rendered = tele.registry.render()
    assert "train_goodput_ratio" in rendered
    # synthetic feeds claim more step time than real wall elapsed, so the
    # ratio is meaningless in magnitude here — what matters is that the
    # gauge left its -1 sentinel and the categories export per label
    ratio = tele.m_goodput.value
    assert ratio > 0.0
    assert tele.m_badput.value("checkpoint_save") == pytest.approx(0.2)

    # /healthz: one liveness + productivity document
    doc = tele.health_document(global_step=33, process_index=0)
    assert doc["status"] == "ok" and doc["global_step"] == 33
    assert doc["goodput_ratio"] is not None and doc["goodput_ratio"] > 0.0
    assert doc["last_event_age_s"] is not None
    assert doc["last_event_age_s"] >= 0.0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_flight_recorder_ring_and_dumps(tmp_path):
    rec = FlightRecorder(
        str(tmp_path / "flightrec_p0.json"), capacity=8, flush_every=3)
    assert rec.last_event_age() is None
    for i in range(20):
        rec.record("step", step=i)
    assert len(rec) == 8  # bounded ring keeps the newest window
    found = newest_flight_record(tmp_path)
    assert found is not None
    _, doc = found
    assert doc["reason"] == "periodic"  # the every-3-records auto flush
    # a terminal dump overrides with its reason and the full current ring
    rec.dump("watchdog", label="train step 19")
    _, doc = newest_flight_record(tmp_path)
    assert doc["reason"] == "watchdog"
    assert [e["step"] for e in doc["events"]] == list(range(12, 20))
    lines = timeline_lines(doc, last=4)
    assert len(lines) == 4 and "step=19" in lines[-1]
    assert rec.last_event_age() is not None


@pytest.mark.unit
def test_newest_flight_record_picks_latest_and_skips_garbage(tmp_path):
    (tmp_path / "flightrec_torn.json").write_text("{ torn")
    (tmp_path / "flightrec_notdict.json").write_text("[1]")
    a = FlightRecorder.open_in(tmp_path, process_index=0)
    a.record("step", step=1)
    a.dump("exception")
    b = FlightRecorder.open_in(tmp_path, process_index=0)
    b.record("step", step=2)
    b.dump("clean")
    path, doc = newest_flight_record(tmp_path)
    assert doc["reason"] == "clean"
    assert doc["events"][-1]["step"] == 2
    assert newest_flight_record(tmp_path / "empty-subdir-missing") is None


@pytest.mark.unit
def test_supervisor_diagnosis_includes_flight_timeline(tmp_path):
    """The exit classifier reads the newest dump back: a crash-loop
    diagnosis carries the last-K-step timeline, and attempt boundaries
    land in the goodput ledger."""
    from ml_recipe_tpu.resilience.supervisor import RetryPolicy, Supervisor

    rec = FlightRecorder.open_in(tmp_path, process_index=0)
    for i in range(5):
        rec.record("step", step=i, total_s=0.1)
    rec.record("slow_step", step=4, attribution="device")
    rec.dump("exception", error="boom")

    ledger_path = tmp_path / GOODPUT_FILENAME
    result = Supervisor(
        lambda i: 1,  # every attempt crashes
        progress=lambda: None,
        policy=RetryPolicy(max_restarts=3, crash_loop_window=2,
                           backoff_base=0.0),
        sleep=lambda s: None,
        ledger_path=ledger_path,
        flight_dir=tmp_path,
    ).run()
    assert result.status == "crash-loop"
    assert "Flight recorder" in result.diagnosis
    assert "slow_step" in result.diagnosis
    assert "attribution=device" in result.diagnosis
    events = read_ledger(ledger_path)
    assert [e["ev"] for e in events] == [
        "attempt_start", "attempt_end", "attempt_start", "attempt_end"]
    assert events[1]["outcome"] == "crash" and events[1]["returncode"] == 1


# ---------------------------------------------------------------------------
# pod-scope aggregation
# ---------------------------------------------------------------------------


def _host_telemetry(steps, device_s):
    tele = TrainTelemetry()
    for i in range(steps):
        tele.observe_step(i, data_wait_s=0.0, host_s=0.0, device_s=device_s)
    return tele


def test_pod_aggregation_merges_two_live_exporters(tmp_path):
    """Acceptance: /metrics/pod merges >= 2 exporters with correct
    sum/min/max and skew gauges — over real HTTP, served as an extra
    route on a third (process-0) exporter."""
    tele_a = _host_telemetry(4, 0.1)   # fast host
    tele_b = _host_telemetry(8, 0.3)   # slow host
    exp_a = MetricsExporter(tele_a.registry, port=0, host="127.0.0.1").start()
    exp_b = MetricsExporter(tele_b.registry, port=0, host="127.0.0.1").start()
    primary = MetricsExporter(Registry(), port=0, host="127.0.0.1").start()
    try:
        targets = [f"127.0.0.1:{exp_a.port}", f"127.0.0.1:{exp_b.port}"]
        aggregator = PodAggregator(targets)
        primary.add_route("/metrics/pod", aggregator.render)
        url = f"http://127.0.0.1:{primary.port}/metrics/pod"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            text = resp.read().decode()

        assert "pod_hosts 2" in text
        assert "pod_hosts_unreachable 0" in text
        assert 'train_steps_total_pod{agg="sum"} 12' in text
        assert 'train_steps_total_pod{agg="min"} 4' in text
        assert 'train_steps_total_pod{agg="max"} 8' in text
        # histograms merge bucket-wise: pod count = 4 + 8
        assert "train_step_seconds_pod_count 12" in text
        # per-host view carries every sample host-labeled
        for target in targets:
            assert f'train_steps_total{{host="{target}"}}' in text

        # derived straggler gauges from the per-host mean step times
        types, samples = parse_prometheus_text(text)
        scalars = {n: v for n, labels, v in samples if not labels}
        assert scalars["pod_slowest_host_step_seconds"] == pytest.approx(
            0.3, rel=1e-6)
        assert scalars["pod_step_time_skew_seconds"] == pytest.approx(
            0.2, rel=1e-6)
    finally:
        exp_a.close()
        exp_b.close()
        primary.close()


def test_pod_aggregation_degrades_on_dead_host(tmp_path):
    tele = _host_telemetry(2, 0.1)
    exp = MetricsExporter(tele.registry, port=0, host="127.0.0.1").start()
    try:
        # a port nothing listens on: the page must render with the host
        # counted unreachable (that is when someone is looking at it)
        aggregator = PodAggregator(
            [f"127.0.0.1:{exp.port}", "127.0.0.1:1"], timeout=0.5)
        text = aggregator.render()
        assert "pod_hosts 1" in text
        assert "pod_hosts_unreachable 1" in text
        assert 'train_steps_total_pod{agg="sum"} 2' in text
    finally:
        exp.close()


@pytest.mark.unit
def test_exporter_add_route_reserved_paths():
    exporter = MetricsExporter(Registry(), port=0, host="127.0.0.1")
    with pytest.raises(ValueError):
        exporter.add_route("/metrics", lambda: "")
    with pytest.raises(ValueError):
        exporter.add_route("/healthz", lambda: "")
    exporter.close()


# ---------------------------------------------------------------------------
# trace merge script
# ---------------------------------------------------------------------------


def _load_merge_traces_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "merge_traces", _REPO / "scripts" / "merge_traces.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.unit
def test_merge_traces_aligns_and_labels(tmp_path):
    """Two per-host trace files merge onto one timeline: distinct pids,
    process_name metadata per host, and timestamps shifted by the
    wall-clock origin anchors the TraceWriter now records."""
    a = TraceWriter(str(tmp_path / "train_trace_p0.json"))
    with a.span("step", cat="train"):
        pass
    a.flush()
    b = TraceWriter(str(tmp_path / "train_trace_p1.json"))
    with b.span("step", cat="train"):
        pass
    b.flush()
    # skew host b's wall anchor by exactly 2s
    doc_b = json.loads((tmp_path / "train_trace_p1.json").read_text())
    doc_b["otherData"]["origin_unix"] = (
        json.loads((tmp_path / "train_trace_p0.json").read_text())
        ["otherData"]["origin_unix"] + 2.0
    )
    (tmp_path / "train_trace_p1.json").write_text(json.dumps(doc_b))

    mod = _load_merge_traces_module()
    out = tmp_path / "pod_trace.json"
    rc = mod.main([
        str(tmp_path / "train_trace_p0.json"),
        str(tmp_path / "train_trace_p1.json"),
        "-o", str(out), "--labels", "host0,host1",
    ])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["otherData"]["aligned"] is True
    metas = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in metas} == {"host0", "host1"}
    steps = [e for e in merged["traceEvents"] if e["name"] == "step"]
    assert {e["pid"] for e in steps} == {0, 1}
    ts0 = next(e["ts"] for e in steps if e["pid"] == 0)
    ts1 = next(e["ts"] for e in steps if e["pid"] == 1)
    assert ts1 - ts0 == pytest.approx(2e6, rel=0.5)  # ~2s in microseconds


@pytest.mark.unit
def test_time_profiler_is_the_trace_plane_decorator(tracer):
    """Satellite: utils.profiler.time_profiler is a shim over the span
    plane — the log line survives AND a cat='profile' span is emitted."""
    from ml_recipe_tpu.utils import profiler

    assert profiler.time_profiler is trace_mod.time_profiler

    @profiler.time_profiler
    def busy_unit():
        return 42

    assert busy_unit() == 42
    events = _validate_chrome_trace(tracer.close())
    spans = [e for e in events if e["name"] == "busy_unit"]
    assert spans and spans[0]["cat"] == "profile"


# ---------------------------------------------------------------------------
# acceptance: supervised chaos run — kill mid-run, auto-resume, ledger +
# flight recorder through the REAL Supervisor and fault registry
# ---------------------------------------------------------------------------


_LEDGER_CHILD = textwrap.dedent(
    """
    import os, sys, time
    import numpy as np

    from ml_recipe_tpu.resilience import faults
    from ml_recipe_tpu.metrics.flightrec import FlightRecorder
    from ml_recipe_tpu.metrics.goodput import GOODPUT_FILENAME, GoodputLedger
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict, peek_global_step, save_state_dict_sharded,
    )

    run_dir = sys.argv[1]
    n_steps = int(sys.argv[2])
    ckpt = os.path.join(run_dir, "state.ckpt")

    params = {"w": np.zeros(4, dtype=np.float32)}
    start = 0
    if peek_global_step(ckpt) is not None:
        params, _, _, got = load_state_dict(ckpt, params=params)
        start = got or 0

    ledger = GoodputLedger(
        os.path.join(run_dir, GOODPUT_FILENAME), flush_every=1)
    rec = FlightRecorder.open_in(run_dir, flush_every=1, capacity=64)
    ledger.note_run_start(start + 1)
    rec.record("run_start", step=start + 1)
    for step in range(start + 1, n_steps + 1):
        faults.fire("trainer.step")
        t0 = time.perf_counter()
        time.sleep(0.02)  # the "device work" of this step
        params = {"w": params["w"] + 1.0}
        ledger.note_step(
            step, wall_s=time.perf_counter() - t0, data_wait_s=0.002,
            compile=(step == start + 1),
        )
        rec.record("step", step=step)
        if step % 2 == 0:  # checkpoint every OTHER step: a mid-stride
            t1 = time.perf_counter()            # kill forces recompute
            save_state_dict_sharded(ckpt, params=params, global_step=step)
            ledger.note_checkpoint("save", time.perf_counter() - t1)
            rec.record("checkpoint_save", step=step)
    ledger.note_run_end(n_steps)
    rec.record("run_end", step=n_steps)
    rec.dump("clean")
    print(f"DONE step={n_steps}")
    """
)

_FAULT_STEP = 4  # arrival the drill kill fires at (steps 1..3 complete)


def test_chaos_ledger_accounts_save_crash_resume_cycle(tmp_path):
    """Acceptance: a supervised run killed mid-stride via --fault_plan and
    auto-resumed produces a ledger whose categories sum to total
    wall-clock within 1%%, a goodput ratio < 1 with nonzero
    restart_downtime AND recompute badput, and a flight-recorder dump
    whose last event precedes the injected fault."""
    from ml_recipe_tpu.resilience.faults import KILL_EXIT_CODE
    from ml_recipe_tpu.resilience.supervisor import RetryPolicy, Supervisor
    from ml_recipe_tpu.train.checkpoint import peek_global_step

    run_dir = tmp_path / "chaos"
    run_dir.mkdir()
    script = run_dir / "child.py"
    script.write_text(_LEDGER_CHILD)
    log = run_dir / "child.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULTS"] = f"trainer.step:kill@{_FAULT_STEP}!once"
    env["MLRT_FAULT_STATE"] = str(run_dir / "fault-state")
    env["PYTHONPATH"] = str(_REPO) + os.pathsep + env.get("PYTHONPATH", "")

    def launch(attempt_i):
        fh = open(log, "ab")
        return subprocess.Popen(
            [sys.executable, str(script), str(run_dir), "6"],
            env=env, cwd=str(_REPO), stdout=fh, stderr=fh,
        )

    ckpt = str(run_dir / "state.ckpt")
    ledger_path = run_dir / GOODPUT_FILENAME
    result = Supervisor(
        launch,
        progress=lambda: peek_global_step(ckpt),
        policy=RetryPolicy(max_restarts=3, backoff_base=0.01,
                           backoff_max=0.02, seed=0),
        attempt_timeout=120,
        sleep=time.sleep,
        state_path=run_dir / "supervisor_state.json",
        ledger_path=ledger_path,
        flight_dir=run_dir,
    ).run()
    assert result.status == "clean", log.read_text(errors="replace")
    assert result.outcomes() == ["crash", "clean"]
    assert result.attempts[0].returncode == KILL_EXIT_CODE
    # killed at step 4's start: steps 1-3 ran, newest checkpoint is step 2
    assert result.attempts[0].step_after == 2
    assert peek_global_step(ckpt) == 6

    events = read_ledger(ledger_path)
    kinds = [e["ev"] for e in events]
    assert kinds.count("attempt_start") == 2
    assert kinds.count("attempt_end") == 2
    assert kinds.count("run_start") == 2

    s = summarize_events(events)
    # categories partition total wall-clock (1% acceptance bound; exact
    # by construction of the residual)
    parts = s["productive_s"] + sum(s["badput_s"].values())
    assert parts == pytest.approx(s["total_wall_s"], rel=0.01)
    assert parts == pytest.approx(s["total_wall_s"], rel=1e-9)
    assert 0.0 < s["goodput_ratio"] < 1.0
    # the restart cost both downtime AND a replayed step (step 3 ran in
    # attempt 1, checkpoint was at 2, attempt 2 re-ran it)
    assert s["badput_s"]["restart_downtime"] > 0.0
    assert s["badput_s"]["recompute"] > 0.0
    assert s["recomputed_steps"] == 1
    assert s["badput_s"]["checkpoint_save"] > 0.0
    assert s["badput_s"]["compile_warmup"] > 0.0
    assert s["steps"] == 3 + 4  # attempt 1: steps 1-3; attempt 2: 3-6

    # the crash attempt's periodic flight dump survived the os._exit kill
    # with its last event BEFORE the injected fault...
    dumps = []
    for p in run_dir.glob("flightrec*.json"):
        doc = json.loads(p.read_text())
        dumps.append(doc)
    crash_dumps = [d for d in dumps if d["reason"] == "periodic"]
    assert crash_dumps, [d["reason"] for d in dumps]
    last_steps = [
        e.get("step") for d in crash_dumps for e in d["events"][-1:]
    ]
    assert all(step is not None and step < _FAULT_STEP
               for step in last_steps)
    # ...and the resumed attempt ended with a clean terminal dump
    _, newest = newest_flight_record(run_dir)
    assert newest["reason"] == "clean"
    assert newest["events"][-1]["kind"] == "run_end"

"""The span plane (``metrics/trace.py``) as the host's one timeline: the
always-on record, the profiler's copy, the set-up spans and compile records,
the unblocked step clock, the registry series that read them
(``train/telemetry.py``) and the benchmark's readers of those."""

import glob
import hashlib
import importlib
import json
import logging
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_recipe_tpu.metrics import trace
from ml_recipe_tpu.metrics.trace import SpanRecord, TraceWriter
from ml_recipe_tpu.train import trainer as trainer_mod
from ml_recipe_tpu.train.telemetry import (
    TrainTelemetry,
    covering_phase,
    setup_seconds,
    step_intervals,
)
from ml_recipe_tpu.utils import platform

from test_dp_equivalence import ONE_CHIP_STEP_SHA256, _step_args
from test_trainer import _make_trainer, _param_snapshot

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

SETUP_READERS = ("model_init_s", "preflight_s", "trace_lower_s",
                 "first_step_s")
CLOCK_READERS = ("step_interval_ms", "step_interval_max_ms")


@pytest.fixture(autouse=True)
def fresh_record():
    trace.clear_record()
    yield
    trace.install(None)
    trace.clear_record()


def _named(records, cat, name):
    return [r for r in records if r.cat == cat and r.name == name]


# -- the record --------------------------------------------------------------------

def test_a_span_is_recorded_with_no_tracer_installed():
    assert trace.current() is None
    with trace.span("dispatch", cat="train", args={"step": 7}) as held:
        pass
    trace.complete("first_step", 1.0, 3.5, cat="setup", args={"step": 0})
    (dispatch,) = trace.recent("train")
    assert dispatch == SpanRecord("dispatch", "train", held.t0, held.t1,
                                  threading.get_ident(), None, {"step": 7})
    assert 0.0 <= dispatch.seconds < 1.0
    (first,) = trace.recent("setup")
    assert (first.t0, first.t1, first.seconds) == (1.0, 3.5, 2.5)
    assert [r.name for r in trace.recent()] == ["first_step", "dispatch"]
    assert trace.recent("serve") == []
    trace.clear_record()
    assert trace.recent() == []


def test_parent_is_the_enclosing_span_of_the_same_thread():
    seen = {}

    def elsewhere():
        with trace.span("place", cat="train"):
            trace.complete("timed", 0.0, 1.0, cat="train")
        seen["thread"] = threading.get_ident()

    with trace.span("preflight", cat="setup"):
        with trace.span("preflight_attempt", cat="setup",
                        args={"split": 2}) as attempt:
            worker = threading.Thread(target=elsewhere)
            worker.start()
            worker.join(timeout=30)
            assert not worker.is_alive()
            trace.complete("lower", 0.0, 1.0, cat="compile")
            attempt.args["verdict"] = "fits"
    with trace.span("after", cat="setup"):
        pass
    by_name = {r.name: r for r in trace.recent()}
    assert by_name["preflight"].parent is None
    assert by_name["preflight_attempt"].parent == "setup:preflight"
    assert by_name["preflight_attempt"].args == {"split": 2,
                                                 "verdict": "fits"}
    assert by_name["lower"].parent == "setup:preflight_attempt"
    # another thread's spans know nothing of this thread's
    assert by_name["place"].parent is None
    assert by_name["place"].thread == seen["thread"] != threading.get_ident()
    assert by_name["timed"].parent == "train:place"
    assert by_name["after"].parent is None      # the stack unwound


def test_the_record_is_bounded_a_category(monkeypatch):
    monkeypatch.setattr(trace, "_RECORD_MAX", 8)
    with trace.span("init_model", cat="setup"):
        pass
    for step in range(20):
        with trace.span("dispatch", cat="train", args={"step": step}):
            pass
    assert [r.args["step"] for r in trace.recent("train")] == list(
        range(12, 20))
    # a flood of step spans pushes no set-up span out
    assert [r.name for r in trace.recent("setup")] == ["init_model"]


def test_an_installed_tracer_gets_the_same_intervals(tmp_path):
    writer = trace.install(TraceWriter(str(tmp_path / "t.json")))
    with trace.span("consume", cat="train", args={"step": 3}) as held:
        pass
    trace.complete("step", held.t0, held.t1, cat="train", args={"step": 3})
    trace.install(None)
    with open(writer.close()) as fh:
        events = json.load(fh)["traceEvents"]
    assert [(e["name"], e["cat"], e["args"]) for e in events] == [
        ("consume", "train", {"step": 3}), ("step", "train", {"step": 3})]
    assert events[0]["dur"] == pytest.approx(1e6 * (held.t1 - held.t0))
    assert len(trace.recent("train")) == 2


def test_a_profiler_session_holds_the_span_on_a_host_line(tmp_path):
    """Under ``jax.profiler`` the program's phases lie in the same
    ``.xplane.pb`` as the device's operations, under ``mlrt:<cat>:<name>``
    with their ``step``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("dispatch", cat="train", args={
                "step": 41, "not_a_stat": object()}):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [
        (plane.name, event.name, dict(event.stats))
        for plane in ProfileData.from_file(found).planes
        for line in plane.lines for event in line.events
        if event.name.startswith("mlrt:")]
    assert [(e[1], e[2]) for e in events] == [
        ("mlrt:train:dispatch", {"step": 41})]
    assert events[0][0].startswith("/host")


def test_compile_stages_become_records(monkeypatch):
    """``utils/platform.py``'s one listener: tracing and lowering stand apart
    from the compile (or cache read), each named by its function."""
    platform.record_compile_spans()
    platform.record_compile_spans()     # once a process, whoever asks

    def fresh_program(x):
        return jnp.tanh(x) * 3.25 + 0.125

    jax.jit(fresh_program)(jnp.ones(5)).block_until_ready()
    mine = [r for r in trace.recent("compile")
            if "fresh_program" in str(r.args["fun"])]
    assert [r.name for r in mine] == ["trace", "lower", "backend"]
    assert all(r.t1 > r.t0 for r in mine)
    assert mine[0].t1 <= mine[1].t1 <= mine[2].t1


# -- the reductions ------------------------------------------------------------------

def _record(name, cat, t0, t1, *, thread=1, parent=None, **args):
    return SpanRecord(name, cat, t0, t1, thread, parent, args or None)


def test_setup_seconds_from_hand_made_records():
    records = [
        _record("init_model", "setup", 0.0, 4.0),
        _record("trace", "compile", 1.0, 2.0, fun="init"),      # 1.0
        _record("preflight", "setup", 10.0, 13.0),
        _record("preflight", "setup", 13.0, 18.0),
        _record("trace", "compile", 10.0, 14.0, fun="train_step"),
        _record("trace", "compile", 11.0, 12.0, fun="inner"),   # nested: no more
        _record("backend", "compile", 12.5, 13.5, fun="probe"),  # a hole of 1.0
        _record("lower", "compile", 14.0, 15.0, fun="train_step"),
        _record("backend", "compile", 15.0, 17.0, fun="train_step"),
        _record("first_step", "setup", 18.0, 20.5, step=0),
        _record("trace", "compile", 30.0, 31.0, fun="eval_step"),  # too late
    ]
    assert setup_seconds(records) == {
        "init_model": 4.0, "preflight": 8.0, "first_step": 2.5,
        "trace_lower": pytest.approx(1.0 + 4.0 - 1.0 + 1.0)}
    assert setup_seconds([]) == {
        "init_model": 0.0, "preflight": 0.0, "trace_lower": 0.0,
        "first_step": 0.0}


def _consume(step, epoch, t1, *, blocked=False, thread=1):
    return SpanRecord("consume", "train", t1 - 0.01, t1, thread, None,
                      {"step": step, "epoch": epoch, "blocked": blocked})


def test_step_intervals_leave_out_an_epochs_first_two_steps():
    records = (
        [_consume(s, 1, 10.0 + s) for s in range(5)]            # steps 0-4
        + [_consume(5 + s, 2, 100.0 + 2 * s) for s in range(4)]  # steps 5-8
        + [_consume(9 + s, 3, 200.0 + s, blocked=True) for s in range(4)])
    found = step_intervals(records)
    assert [(i.step, i.seconds) for i in found] == [
        (2, 1.0), (3, 1.0), (4, 1.0), (7, 2.0), (8, 2.0)]
    assert (found[0].t0, found[0].t1, found[0].thread) == (11.0, 12.0, 1)
    # another trainer's boundaries, before this one was built
    assert [i.step for i in step_intervals(records, since=50.0)] == [7, 8]


@pytest.mark.parametrize("spans,want", [
    ([("data_wait", 0.0, 0.1), ("dispatch", 0.1, 0.2), ("consume", 0.2, 1.0)],
     ("consume", 0.8)),
    # a loader that stalls: the wait, less the placement inside it
    ([("data_wait", 0.0, 0.9), ("place", 0.6, 0.9, "train:data_wait"),
      ("consume", 0.9, 1.0)], ("data_wait", 0.6)),
    # a pause no phase of the loop covers
    ([("dispatch", 0.0, 0.1), ("consume", 0.8, 1.0)], ("uncovered", 0.7)),
    # a phase that began before the interval counts for what lies inside
    ([("consume", -5.0, 0.3), ("after_epoch", 0.3, 1.0)],
     ("after_epoch", 0.7)),
])
def test_covering_phase_names_what_held_an_interval(spans, want):
    records = [_record(name, "train", t0, t1, parent=(rest or [None])[0])
               for name, t0, t1, *rest in spans]
    records.append(_record("place", "train", 0.0, 1.0, thread=2))   # prefetch
    records.append(_record("step", "train", 0.0, 1.0))              # no phase
    phase, held = covering_phase(records, 0.0, 1.0, thread=1)
    assert (phase, held) == (want[0], pytest.approx(want[1]))


# -- a tiny trainer run ----------------------------------------------------------------

@pytest.fixture
def run(tmp_path, monkeypatch):
    """Three epochs of six steps on ``data:8``; a pre-flight whose first
    attempt reads over the limit; a telemetry attached before the third."""
    readings = iter([2000, 10])
    monkeypatch.setattr(trainer_mod, "_device_hbm_bytes", lambda: 1000)
    monkeypatch.setattr(trainer_mod, "_preflight_bytes",
                        lambda analysis: next(readings))
    trainer, _ = _make_trainer(tmp_path, train_len=96, n_epochs=3,
                               dropout=0.0)
    telemetry = TrainTelemetry()

    def attach(epoch_i):
        if epoch_i == 2:
            trainer.telemetry = telemetry

    trainer.train(after_epoch_funcs=[attach])
    assert trainer.global_step == 18 and trainer.batch_split == 2
    return trainer, telemetry, trace.recent()


def test_a_run_leaves_each_setup_span_once_and_an_attempt_a_split(run):
    _, _, records = run
    for name in ("trainer_init", "preflight", "first_step"):
        assert len(_named(records, "setup", name)) == 1, name
    attempts = _named(records, "setup", "preflight_attempt")
    assert [(a.args["split"], a.args["verdict"], a.parent)
            for a in attempts] == [(1, "over", "setup:preflight"),
                                   (2, "fits", "setup:preflight")]
    (preflight,) = _named(records, "setup", "preflight")
    (first,) = _named(records, "setup", "first_step")
    assert preflight.t0 <= attempts[0].t0 and attempts[1].t1 <= preflight.t1
    # the first step starts where the pre-flight ended and ends at its boundary
    (boundary,) = [r for r in _named(records, "train", "consume")
                   if r.args["step"] == 0]
    assert preflight.t1 <= first.t0 and first.t1 == boundary.t1
    assert first.args == {"step": 0}


def test_every_step_has_its_four_phases_and_the_hooks_their_span(run):
    _, _, records = run
    for name in ("data_wait", "place", "dispatch", "consume", "step"):
        steps = [r.args["step"] for r in _named(records, "train", name)]
        if name == "data_wait":     # and an epoch's last wait, which finds
            assert len(steps) == 18 + 3     # the data at its end
        assert sorted(set(steps) - {18}) == list(range(18)), name
    assert [(r.args["epoch"], r.args["step"])
            for r in _named(records, "train", "after_epoch")] == [
        (1, 6), (2, 12), (3, 18)]
    dispatch = {r.args["step"]: r for r in _named(records, "train", "dispatch")}
    boundary = {r.args["step"]: r for r in _named(records, "train", "consume")}
    whole = {r.args["step"]: r for r in _named(records, "train", "step")}
    for step in range(18):
        assert dispatch[step].t1 <= boundary[step].t1
        assert (whole[step].t0, whole[step].t1) == (
            dispatch[step].t0, boundary[step].t1)
        assert boundary[step].args["blocked"] is (step >= 12)


def test_the_step_clock_has_every_step_but_an_epochs_first_two(run):
    trainer, _, records = run
    clock = step_intervals(records, since=trainer._built_at)
    assert [i.step for i in clock] == [2, 3, 4, 5, 8, 9, 10, 11]
    boundary = {r.args["step"]: r.t1 for r in _named(records, "train", "consume")}
    for interval in clock:
        assert interval.seconds == pytest.approx(
            boundary[interval.step] - boundary[interval.step - 1])
        assert interval.seconds > 0
    assert step_intervals(records, since=boundary[17] + 1.0) == []


def test_a_telemetry_attached_late_takes_in_the_earlier_epochs_once(run):
    trainer, telemetry, records = run
    clock = step_intervals(records)
    # epochs 1 and 2 from the step clock, epoch 3 from the blocked walls
    # (its first two steps left out there too)
    assert telemetry.m_step_interval.count == len(clock) + 4 == 12
    assert telemetry.m_step.count == 6
    walls = telemetry.m_step_interval.sum - sum(i.seconds for i in clock)
    assert 0 < walls <= telemetry.m_step.sum
    telemetry.observe_span_record(since=trainer._built_at)
    assert telemetry.m_step_interval.count == 12
    # the set-up gauges say what the record says
    want = setup_seconds(trace.recent("setup") + trace.recent("compile"))
    assert {k: g.value for k, g in telemetry.m_setup.items()} == want
    assert want["preflight"] > 0 and want["first_step"] > 0
    assert want["init_model"] == 0.0        # compose.init_model never ran
    # a telemetry built after the run reads the same set-up and no interval
    late = TrainTelemetry()
    assert {k: g.value for k, g in late.m_setup.items()} == want
    assert late.m_step_interval.count == 0


def test_compose_wraps_model_and_dataset_init(tmp_path):
    from ml_recipe_tpu.compose import init_datasets, init_model
    from test_compose import _model_params, _trainer_params

    _, _, tokenizer = init_model(_model_params(tmp_path, model="bert-tiny"))
    init_datasets(_trainer_params(dummy_dataset=True, max_seq_len=32,
                                  max_question_len=8), tokenizer=tokenizer)
    assert [r.name for r in trace.recent("setup")] == [
        "init_model", "init_datasets"]
    assert setup_seconds(trace.recent())["init_model"] > 0


# -- tracing on changes no arithmetic and blocks no step ---------------------------------

def test_trace_spans_on_blocks_no_step_and_moves_no_number(
        tmp_path, monkeypatch):
    """With a ``TraceWriter`` installed (``--trace_spans``) the loop waits for
    no step it did not wait for before, the step program is the pinned one,
    and the trajectory is the plain run's bit for bit."""
    blocked = []
    wait = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready", lambda tree: blocked.append(1) or wait(tree))
    runs = {}
    for spans in (False, True):
        if spans:
            trace.install(TraceWriter(str(tmp_path / "spans.json")))
        (tmp_path / f"spans_{spans}").mkdir()
        pinned, _ = _make_trainer(      # the pin's own trainer
            tmp_path / f"spans_{spans}", mesh_spec="data:1", dropout=0.1,
            batch_split=2)
        text = pinned._build_train_step().lower(*_step_args(pinned)).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == ONE_CHIP_STEP_SHA256
        trainer, _ = _make_trainer(
            tmp_path / f"spans_{spans}", mesh_spec="data:1", dropout=0.1,
            batch_split=2, n_epochs=2, train_len=64, device_prefetch=0)
        losses = []
        trainer.on_train_metrics = lambda meters, step: losses.append(
            float(meters["loss"]()))
        before = len(blocked)
        trainer.train()
        assert len(blocked) == before, "a step was waited for"
        runs[spans] = (losses, _param_snapshot(trainer.params))
    assert runs[True][0] == runs[False][0] and len(runs[True][0]) == 8
    for a, b in zip(jax.tree_util.tree_leaves(runs[False][1]),
                    jax.tree_util.tree_leaves(runs[True][1])):
        np.testing.assert_array_equal(a, b)


def test_a_slow_interval_is_logged_once_with_the_phase_that_held_it(
        tmp_path, caplog):
    """The unblocked clock feeds ``metrics/anomaly.py``'s detector: a loader
    that stalls for one step shows as one warning naming ``data_wait``."""
    import time

    trainer, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.0,
                               train_len=16 * 24, device_prefetch=0)
    plain = type(trainer.train_dataloader).__iter__

    def stalling(loader):
        for n, batch in enumerate(plain(loader)):
            if n == 20:
                time.sleep(1.5)
            yield batch

    type(trainer.train_dataloader).__iter__ = stalling
    try:
        with caplog.at_level(logging.WARNING,
                             logger="ml_recipe_tpu.train.trainer"):
            trainer.train()
    finally:
        type(trainer.train_dataloader).__iter__ = plain
    import re

    slow = [r.getMessage() for r in caplog.records
            if "SLOW STEP INTERVAL" in r.getMessage()]
    # (a loaded machine may stretch another step; the stall is told by its
    # length: once, and held by the wait for the batch)
    stalls = [m for m in slow
              if float(re.search(r": ([\d.]+) ms between", m).group(1)) > 1400]
    assert len(stalls) == 1, slow
    held = re.search(r"data_wait held ([\d.]+) ms", stalls[0])
    assert held and float(held.group(1)) > 1400, stalls


# -- the benchmark's readers ---------------------------------------------------------------

def _read(name, ctx):
    return importlib.import_module(f"perfbench.metrics.{name}").read(ctx)


@pytest.mark.parametrize("name", SETUP_READERS + CLOCK_READERS)
def test_a_reader_on_a_fed_an_empty_and_no_registry(name, capsys):
    fed = TrainTelemetry()
    for step, device_s in enumerate((0.71, 0.72, 0.70, 1.9)):
        fed.observe_step(step, data_wait_s=0.001, host_s=0.009,
                         device_s=device_s, host_overlapped=True)
    for gauge, seconds in zip(fed.m_setup.values(), (11.0, 9.5, 7.25, 2.5)):
        gauge.set(seconds)
    want = {"model_init_s": 11.0, "preflight_s": 9.5, "trace_lower_s": 7.25,
            "first_step_s": 2.5, "step_interval_ms": 716.0,
            "step_interval_max_ms": 1901.0}[name]
    assert _read(name, {"telemetry": fed.registry}) == pytest.approx(want)
    empty = _read(name, {"telemetry": TrainTelemetry().registry})
    assert empty == (0.0 if name in SETUP_READERS else None)
    # a context with no telemetry, a registry without the series (a parent
    # commit's program): nothing, and no exception
    from ml_recipe_tpu.metrics.registry import Registry

    assert _read(name, {}) is None
    assert _read(name, {"telemetry": Registry()}) is None
    # with nothing recorded (these contexts) no table goes out
    assert capsys.readouterr().out == ""


def test_the_readers_lines_hold_the_tables_they_read(run, capsys):
    trainer, telemetry, _ = run
    ctx = {"telemetry": telemetry.registry}
    got = {name: _read(name, ctx) for name in SETUP_READERS + CLOCK_READERS}
    assert got["preflight_s"] > 0 and got["first_step_s"] > 0
    assert 0 < got["step_interval_ms"] <= got["step_interval_max_ms"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [list(x) for x in lines] == [["setup_spans"], ["step_clock"]]
    table = lines[0]["setup_spans"]
    assert [r["span"] for r in table["spans"]] == [
        "trainer_init", "preflight", "preflight_attempt",
        "preflight_attempt", "first_step"]
    by_span = {r["span"]: r for r in table["spans"]}
    # the pre-flight's own time is what its attempts leave
    attempts = sum(r["s"] for r in table["spans"]
                   if r["span"] == "preflight_attempt")
    assert by_span["preflight"]["self_s"] == pytest.approx(
        by_span["preflight"]["s"] - attempts)
    assert by_span["preflight"]["trace_lower_s"] > 0
    assert table["spans"][3]["verdict"] == "fits"
    parts = (table["before_first_span_s"] + table["setup_spans_s"]
             + table["step_spans_s"] + table["uncovered_s"])
    assert parts == pytest.approx(table["start_to_window_s"])
    assert 0 <= table["uncovered_s"] < 0.2 * table["start_to_window_s"]
    clock = lines[1]["step_clock"]
    assert clock["intervals"] == 8 and clock["blocked_steps"] == 6
    assert len(clock["longest"]) == 5
    assert clock["longest"][0]["ms"] >= clock["longest"][-1]["ms"]
    assert {x["phase"] for x in clock["longest"]} <= {
        "data_wait", "place", "dispatch", "consume", "uncovered"}
    # once a run: a second reading prints nothing more
    _read("preflight_s", ctx), _read("step_interval_ms", ctx)
    assert capsys.readouterr().out == ""


# slow, as the other rehearsals are (tests/yardstick/test_perfbench_runs.py
# says why): a subprocess of jax compiles on one core
@pytest.mark.slow
def test_a_traced_rehearsal_still_ends_in_its_last_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, str(REPO / "perfbench" / "run.py"), "--workload",
         "base-train-full512", "--seed", "3000000011", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=str(REPO), env=env, text=True, capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["metrics"] == {}

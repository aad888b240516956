"""Chaos suite for the fault-tolerance subsystem (resilience/).

Every scenario is DETERMINISTIC: faults fire on counted arrivals at named
sites (no timing races, no randomness), so a kill-restart-resume drill
replays identically run after run — the acceptance bar for trusting any of
these recovery paths.

Three layers of coverage:
- unit: FaultPlan grammar/counters, retry helper, watchdog deadlines,
  supervisor classification/backoff/crash-loop logic, checkpoint crc32 and
  interrupted-swap recovery windows;
- loader/predictor satellites: worker traceback preservation, transient
  read retry, join-timeout visibility;
- end-to-end: a real child process doing sharded checkpoint saves under an
  armed fault plan, driven by the real Supervisor — kill between shard and
  manifest writes, a stalled step tripping the watchdog, and an
  unrecoverable crash-loop.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from ml_recipe_tpu.resilience import faults as faults_mod
from ml_recipe_tpu.resilience.faults import (
    KILL_EXIT_CODE,
    FaultError,
    FaultPlan,
    retry_transient,
)
from ml_recipe_tpu.resilience.supervisor import (
    PREEMPT_EXIT_CODE,
    RetryPolicy,
    Supervisor,
    build_child_argv,
    classify_exit,
)
from ml_recipe_tpu.resilience.watchdog import WATCHDOG_EXIT_CODE, Watchdog

pytestmark = pytest.mark.chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# FaultPlan
# ---------------------------------------------------------------------------


def test_fault_plan_grammar():
    plan = FaultPlan.parse(
        "ckpt.pre_manifest:kill@2!once; loader.read:raise@1x3;"
        "trainer.step:stall~5;dist.barrier:raise@4x*"
    )
    kinds = [(s.site, s.kind, s.hit, s.count, s.seconds, s.once) for s in plan.specs]
    assert kinds == [
        ("ckpt.pre_manifest", "kill", 2, 1, None, True),
        ("loader.read", "raise", 1, 3, None, False),
        ("trainer.step", "stall", 1, 1, 5.0, False),
        ("dist.barrier", "raise", 4, -1, None, False),
    ]


@pytest.mark.parametrize(
    "bad", ["typo.site:kill", "loader.read:explode", "loader.read", "a:b@0"]
)
def test_fault_plan_rejects_typos(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_fault_plan_counted_arrivals():
    plan = FaultPlan.parse("loader.read:raise@2x2")
    plan.fire("loader.read")  # arrival 1: armed at 2 -> no fire
    for _ in range(2):        # arrivals 2, 3 fire
        with pytest.raises(FaultError):
            plan.fire("loader.read")
    plan.fire("loader.read")  # arrival 4: window passed
    assert plan.hits("loader.read") == 4
    plan.fire("trainer.step")  # unarmed site: fast-path no-op (uncounted)
    assert plan.hits("trainer.step") == 0


def test_fault_plan_once_survives_restart(tmp_path):
    """!once state lives in a marker file: a 'restarted' plan (fresh
    counters, same state dir) must NOT re-fire — that is what lets a
    kill-drill converge instead of crash-looping."""
    state = str(tmp_path / "fault-state")
    plan1 = FaultPlan.parse("loader.read:raise@1!once", state_dir=state)
    with pytest.raises(FaultError):
        plan1.fire("loader.read")
    plan2 = FaultPlan.parse("loader.read:raise@1!once", state_dir=state)
    plan2.fire("loader.read")  # marker present: skipped
    assert plan2.hits("loader.read") == 1


def test_fault_once_is_single_shot_under_concurrency(tmp_path):
    """Concurrent loader threads inside the active window must resolve a
    !once spec to exactly ONE firing (the check-and-record is under the
    plan lock) — the determinism contract at the one multi-threaded site."""
    plan = FaultPlan.parse(
        "loader.read:raise@1x2!once", state_dir=str(tmp_path / "state")
    )
    start = threading.Barrier(2)
    raises = []

    def arrive():
        start.wait()
        try:
            plan.fire("loader.read")
        except FaultError:
            raises.append(1)

    threads = [threading.Thread(target=arrive) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(raises) == 1


def test_global_install_and_site_noop():
    faults_mod.install_plan("trainer.step:raise@1")
    try:
        with pytest.raises(FaultError):
            faults_mod.fire("trainer.step")
        faults_mod.fire("trainer.eval_step")  # unarmed: no-op
    finally:
        faults_mod.install_plan(None)
    faults_mod.fire("trainer.step")  # disarmed: no-op


def test_retry_transient_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_transient(flaky, retries=3, sleep=lambda _: None) == "ok"
    assert len(calls) == 3


def test_retry_transient_exhausts_with_original_error():
    def always():
        raise OSError("persistent")

    with pytest.raises(OSError, match="persistent"):
        retry_transient(always, retries=2, sleep=lambda _: None)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------


def _test_watchdog(timeout, fired):
    return Watchdog(
        timeout,
        poll_interval=0.01,
        on_timeout=lambda label: fired.append(label),
        exit_fn=lambda code: fired.append(code),
    )


def test_watchdog_fires_on_missed_deadline(capsys):
    fired = []
    wd = _test_watchdog(0.08, fired)
    try:
        with wd.watch("stuck step"):
            deadline = time.monotonic() + 2.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.01)
    finally:
        wd.stop()
    assert fired == ["stuck step", WATCHDOG_EXIT_CODE]
    err = capsys.readouterr().err
    assert "WATCHDOG" in err and "stuck step" in err
    # the all-thread stack dump names this very test frame
    assert "test_watchdog_fires_on_missed_deadline" in err


def test_watchdog_tick_defers_firing():
    fired = []
    wd = _test_watchdog(1.0, fired)
    try:
        with wd.watch("epoch") as tick:
            for i in range(4):
                tick(f"step {i}")
                time.sleep(0.1)  # each step well under the deadline
    finally:
        wd.stop()
    assert fired == []


def test_watchdog_nested_frames_are_reentrant():
    """An inner (checkpoint-barrier) frame with a long timeout must shadow
    the outer step frame, and popping it must restart the outer clock."""
    fired = []
    wd = _test_watchdog(0.5, fired)
    try:
        with wd.watch("outer"):
            with wd.watch("inner", timeout=30.0):
                time.sleep(1.0)  # outer would have expired; inner shadows it
            time.sleep(0.05)     # outer clock restarted on pop
        assert fired == []
    finally:
        wd.stop()


def test_watchdog_on_beat_hook_is_rate_limited_and_contained(caplog):
    """The elastic child's heartbeat rides the watchdog's own beat: rate-
    limited to min_interval, handed the last noted step, and a hook
    failure degrades heartbeating without touching training."""
    import logging as logging_mod

    fired = []
    wd = _test_watchdog(30.0, fired)
    beats = []
    try:
        wd.add_on_beat(beats.append, min_interval=0.2)
        wd.note_progress(7)               # emits immediately
        with wd.watch("epoch") as tick:
            tick("fast")                  # inside the interval: suppressed
            time.sleep(0.25)
            tick("later")                 # interval elapsed: emits again
        assert beats == [7, 7]

        def boom(step):
            raise RuntimeError("heartbeat disk full")

        wd.add_on_beat(boom, min_interval=0.0)
        with caplog.at_level(logging_mod.ERROR):
            wd.note_progress(8)           # must not raise
        assert "on_beat hook failed" in caplog.text
    finally:
        wd.stop()
    assert fired == []


def test_watchdog_notes_last_step(capsys):
    fired = []
    wd = _test_watchdog(0.08, fired)
    try:
        wd.note_progress(41)
        with wd.watch("stall"):
            deadline = time.monotonic() + 2.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.01)
    finally:
        wd.stop()
    assert "last completed step: 41" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Loader satellites: traceback preservation + transient retry
# ---------------------------------------------------------------------------


class _FlakyDataset:
    """Items are [i, i]; reads of `fail_index` raise OSError `fails` times."""

    def __init__(self, n=8, fail_index=3, fails=2, exc=OSError):
        self.n = n
        self.fail_index = fail_index
        self.fails_left = fails
        self.exc = exc

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.fail_index and self.fails_left > 0:
            self.fails_left -= 1
            raise self.exc(f"injected failure reading item {i}")
        return np.array([i, i], dtype=np.int32)


def test_map_loader_retries_transient_oserror(monkeypatch):
    from ml_recipe_tpu.data.loader import DataLoader, ShardedBatchSampler

    monkeypatch.setattr(time, "sleep", lambda _: None)  # no backoff waits
    ds = _FlakyDataset(n=8, fail_index=3, fails=2)
    sampler = ShardedBatchSampler(8, 4, shuffle=False, drop_last=True)
    loader = DataLoader(
        ds, sampler, lambda items: np.stack(items), n_jobs=2, read_retries=3
    )
    batches = list(loader)
    assert len(batches) == 2 and ds.fails_left == 0
    np.testing.assert_array_equal(
        np.concatenate(batches)[:, 0], np.arange(8)
    )


def test_list_loader_retries_transient_oserror(monkeypatch):
    from ml_recipe_tpu.data.loader import ListDataloader

    monkeypatch.setattr(time, "sleep", lambda _: None)

    class ChunkDS(_FlakyDataset):
        def __getitem__(self, i):
            return [super().__getitem__(i)]

    loader = ListDataloader(ChunkDS(n=6, fails=2), batch_size=2, n_jobs=2)
    chunks = [c for batch in loader for c in batch]
    assert len(chunks) == 6


def test_list_loader_preserves_worker_traceback():
    from ml_recipe_tpu.data.loader import DataLoaderWorkerError, ListDataloader

    class Boom:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            if i == 2:
                raise ValueError("boom at item 2")
            return [np.zeros(1)]

    loader = ListDataloader(Boom(), batch_size=2, n_jobs=2)
    with pytest.raises(DataLoaderWorkerError) as exc_info:
        list(loader)
    msg = str(exc_info.value)
    # the WORKER's stack (file/function where it died), not just the message
    assert "boom at item 2" in msg
    assert "worker traceback" in msg and "__getitem__" in msg
    assert isinstance(exc_info.value.__cause__, ValueError)


def test_predictor_shutdown_surfaces_wedged_worker(caplog):
    from ml_recipe_tpu.infer.predictor import (
        WorkerShutdownError,
        _ensure_worker_stopped,
    )

    release = threading.Event()
    wedged = threading.Thread(
        target=release.wait, name="wedged-worker", daemon=True
    )
    wedged.start()
    try:
        with caplog.at_level("WARNING"):
            with pytest.raises(WorkerShutdownError, match="wedged-worker"):
                _ensure_worker_stopped(wedged, timeout=0.1)
        assert "still alive" in caplog.text
        assert "release.wait" in caplog.text or "wait" in caplog.text

        # an exception already in flight must NOT be replaced by the
        # shutdown complaint — warn only
        try:
            raise RuntimeError("original failure")
        except RuntimeError:
            _ensure_worker_stopped(wedged, timeout=0.05)  # no raise
    finally:
        release.set()
        wedged.join(timeout=2)

    done = threading.Thread(target=lambda: None)
    done.start()
    _ensure_worker_stopped(done, timeout=1.0)  # clean exit: no-op


# ---------------------------------------------------------------------------
# Checkpoint: crc32 verification + interrupted-swap windows + peek
# ---------------------------------------------------------------------------


def _tiny_params():
    return {
        "w": np.arange(8, dtype=np.float32),
        "b": np.float32(3.0),
    }


def _save_sharded(path, params, step):
    from ml_recipe_tpu.train.checkpoint import save_state_dict_sharded

    save_state_dict_sharded(path, params=params, global_step=step)


def test_sharded_crc_roundtrip_and_peek(tmp_path):
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict_sharded,
        peek_global_step,
    )

    ckpt = str(tmp_path / "crc.ckpt")
    _save_sharded(ckpt, _tiny_params(), 5)
    assert peek_global_step(ckpt) == 5

    p, _, _, step = load_state_dict_sharded(ckpt, params=_tiny_params())
    assert step == 5
    np.testing.assert_array_equal(p["w"], np.arange(8, dtype=np.float32))


def test_sharded_crc_detects_bit_rot(tmp_path):
    from ml_recipe_tpu.train.checkpoint import (
        TornCheckpointError,
        load_state_dict,
        load_state_dict_sharded,
    )

    ckpt = str(tmp_path / "rot.ckpt")
    _save_sharded(ckpt, _tiny_params(), 5)

    shard = os.path.join(ckpt, "shard-00000.msgpack")
    blob = bytearray(open(shard, "rb").read())
    needle = np.arange(8, dtype=np.float32).tobytes()
    at = blob.find(needle)
    assert at >= 0, "could not locate leaf bytes in the shard file"
    blob[at + 5] ^= 0xFF  # single flipped byte inside the array payload
    open(shard, "wb").write(bytes(blob))

    with pytest.raises(TornCheckpointError, match="crc32"):
        load_state_dict_sharded(ckpt, params=_tiny_params())

    # the --last resume path keeps its warn-and-continue contract: a
    # corrupt checkpoint must not crash startup
    params0 = _tiny_params()
    p, _, _, step = load_state_dict(ckpt, params=params0)
    assert step is None and p is params0


def test_sharded_crc_detects_hand_assembled_mix(tmp_path):
    """Two internally-consistent saves at the SAME step, shard file of one
    placed under the manifest of the other: the step check passes, the
    manifest leaf checksum must not."""
    from ml_recipe_tpu.train.checkpoint import (
        TornCheckpointError,
        load_state_dict_sharded,
    )

    a, b = str(tmp_path / "a.ckpt"), str(tmp_path / "b.ckpt")
    _save_sharded(a, _tiny_params(), 5)
    other = _tiny_params()
    other["w"] = other["w"] + 100.0
    _save_sharded(b, other, 5)

    os.replace(
        os.path.join(b, "shard-00000.msgpack"),
        os.path.join(a, "shard-00000.msgpack"),
    )
    with pytest.raises(TornCheckpointError, match="manifest"):
        load_state_dict_sharded(a, params=_tiny_params())


def test_peek_global_step_variants(tmp_path):
    from ml_recipe_tpu.train.checkpoint import peek_global_step, save_state_dict

    assert peek_global_step(str(tmp_path / "missing.ch")) is None

    single = str(tmp_path / "single.ch")
    save_state_dict(single, params=_tiny_params(), global_step=7)
    assert peek_global_step(single) == 7

    garbage = str(tmp_path / "garbage.ch")
    open(garbage, "wb").write(b"not a checkpoint")
    assert peek_global_step(garbage) is None

    # manifest-less directory (interrupted first sharded save)
    empty_dir = tmp_path / "empty.ckpt"
    empty_dir.mkdir()
    assert peek_global_step(str(empty_dir)) is None


# -- _recover_interrupted_swap windows ----------------------------------------


def _fake_sharded_dir(path, tag, *, manifest=True):
    os.makedirs(path)
    with open(os.path.join(path, "shard-00000.msgpack"), "w") as fh:
        fh.write(tag)
    if manifest:
        with open(os.path.join(path, "manifest.msgpack"), "w") as fh:
            fh.write(tag)


def _tag_of(path):
    with open(os.path.join(path, "shard-00000.msgpack")) as fh:
        return fh.read()


def test_swap_recovery_rolls_forward_complete_staging(tmp_path):
    from ml_recipe_tpu.train.checkpoint import _recover_interrupted_swap

    path = str(tmp_path / "c.ckpt")
    _fake_sharded_dir(path + ".saving", "new", manifest=True)
    _fake_sharded_dir(path + ".old", "old", manifest=True)
    _recover_interrupted_swap(path, path + ".saving", path + ".old")
    assert _tag_of(path) == "new"
    assert not os.path.exists(path + ".saving")


def test_swap_recovery_rolls_back_incomplete_staging(tmp_path):
    from ml_recipe_tpu.train.checkpoint import _recover_interrupted_swap

    path = str(tmp_path / "c.ckpt")
    _fake_sharded_dir(path + ".saving", "new", manifest=False)  # died pre-manifest
    _fake_sharded_dir(path + ".old", "old", manifest=True)
    _recover_interrupted_swap(path, path + ".saving", path + ".old")
    assert _tag_of(path) == "old"


def test_swap_recovery_noop_when_live_checkpoint_exists(tmp_path):
    from ml_recipe_tpu.train.checkpoint import _recover_interrupted_swap

    path = str(tmp_path / "c.ckpt")
    _fake_sharded_dir(path, "live", manifest=True)
    _fake_sharded_dir(path + ".saving", "new", manifest=True)
    _recover_interrupted_swap(path, path + ".saving", path + ".old")
    assert _tag_of(path) == "live"  # untouched
    assert os.path.isdir(path + ".saving")  # debris is the next save's job


def test_swap_recovery_tolerates_losing_the_race(tmp_path, monkeypatch):
    """A concurrent recoverer's rename wins: ours sees FileNotFoundError,
    but the live path exists afterwards — that is success, not an error."""
    from ml_recipe_tpu.train import checkpoint as ckpt_mod

    path = str(tmp_path / "c.ckpt")
    _fake_sharded_dir(path + ".saving", "new", manifest=True)

    real_rename = os.rename

    def racing_rename(src, dst):
        # the competing process completes the recovery first...
        real_rename(src, dst)
        # ...and ours loses: the source is already gone
        raise FileNotFoundError(src)

    monkeypatch.setattr(os, "rename", racing_rename)
    ckpt_mod._recover_interrupted_swap(path, path + ".saving", path + ".old")
    monkeypatch.undo()
    assert _tag_of(path) == "new"


def test_swap_recovery_reraises_genuine_failure(tmp_path, monkeypatch):
    from ml_recipe_tpu.train import checkpoint as ckpt_mod

    path = str(tmp_path / "c.ckpt")
    _fake_sharded_dir(path + ".saving", "new", manifest=True)

    def failing_rename(src, dst):
        raise PermissionError(src)  # path still missing afterwards

    monkeypatch.setattr(os, "rename", failing_rename)
    with pytest.raises(PermissionError):
        ckpt_mod._recover_interrupted_swap(path, path + ".saving", path + ".old")


# ---------------------------------------------------------------------------
# Supervisor unit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rc,outcome",
    [
        (0, "clean"),
        (WATCHDOG_EXIT_CODE, "hang"),
        (PREEMPT_EXIT_CODE, "preempted"),
        (-15, "preempted"),
        (143, "preempted"),
        (-9, "preempted"),
        (1, "crash"),
        (KILL_EXIT_CODE, "crash"),
    ],
)
def test_classify_exit(rc, outcome):
    assert classify_exit(rc) == outcome


def _scripted_supervisor(children, steps, policy):
    child_iter = iter(children)
    step_iter = iter(steps)
    return Supervisor(
        lambda i: next(child_iter),
        progress=lambda: next(step_iter),
        policy=policy,
        sleep=lambda s: None,
    )


def test_supervisor_resumes_after_crash_with_progress():
    # progress() runs before and after every attempt
    res = _scripted_supervisor(
        [1, 0], [None, 1, 1, 2], RetryPolicy(max_restarts=3)
    ).run()
    assert res.status == "clean"
    assert res.outcomes() == ["crash", "clean"]
    assert res.exit_code == 0


def test_supervisor_aborts_crash_loop_with_diagnosis(capsys):
    res = _scripted_supervisor(
        [1, 1, 1, 1], [None] * 8,
        RetryPolicy(max_restarts=5, crash_loop_window=2),
    ).run()
    assert res.status == "crash-loop"
    assert res.outcomes() == ["crash", "crash"]  # aborted at the window
    assert res.exit_code == 1
    assert "crash-loop" in res.diagnosis and "no global_step progress" in res.diagnosis
    assert "crash-loop" in capsys.readouterr().err


def test_supervisor_progress_resets_crash_loop_streak():
    # each failure makes checkpoint progress: never a crash-loop
    res = _scripted_supervisor(
        [1, 1, 0], [None, 1, 1, 2, 2, 3],
        RetryPolicy(max_restarts=5, crash_loop_window=2),
    ).run()
    assert res.status == "clean"


def test_supervisor_exhausts_retry_budget():
    # only NO-progress failures consume the budget; window > budget so the
    # crash-loop detector stays out of the way
    res = _scripted_supervisor(
        [PREEMPT_EXIT_CODE] * 2, [None] * 4,
        RetryPolicy(max_restarts=1, crash_loop_window=5),
    ).run()
    assert res.status == "retries-exhausted"
    assert res.outcomes() == ["preempted", "preempted"]
    assert res.exit_code == 2
    assert "retry budget exhausted" in res.diagnosis


def test_supervisor_progressing_preemptions_do_not_burn_budget():
    """Preemption is the steady state: attempts that failed but ADVANCED
    the checkpoint must not consume the restart budget — a healthy
    preemption-heavy run outlives any fixed max_restarts."""
    children = [PREEMPT_EXIT_CODE] * 5 + [0]
    steps = [None, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6]
    res = _scripted_supervisor(
        children, steps, RetryPolicy(max_restarts=2, crash_loop_window=3)
    ).run()
    assert res.status == "clean"
    assert len(res.attempts) == 6  # far beyond max_restarts + 1


def test_supervisor_backoff_is_seeded_and_bounded():
    policy = RetryPolicy(
        max_restarts=3, backoff_base=1.0, backoff_factor=2.0,
        backoff_max=3.0, jitter=0.1, crash_loop_window=10, seed=7,
    )

    def backoffs():
        # no-progress failures: backoff doubles with the streak (1, 2,
        # then capped at 3), with seeded +-10% jitter
        res = _scripted_supervisor([1, 1, 1, 0], [None] * 8, policy).run()
        return [a.backoff for a in res.attempts]

    b1, b2 = backoffs(), backoffs()
    assert b1 == b2  # deterministic across runs
    for expected, got in zip([1.0, 2.0, 3.0], b1):
        assert expected * 0.9 <= got <= expected * 1.1
    assert b1[-1] == 0.0  # no sleep after the final (clean) attempt


def test_supervisor_forwards_termination_and_stands_down():
    """SIGTERM on the SUPERVISOR forwards to the live child and ends the
    loop after the child exits — never an orphaned trainer racing the next
    submission on the checkpoint directory, never a restart."""
    import signal as signal_mod

    sent = []
    holder = {}

    class FakeChild:
        def send_signal(self, signum):
            sent.append(int(signum))

        def wait(self, timeout=None):
            # the signal lands while the supervisor blocks in wait()
            holder["sup"]._forward_signal(signal_mod.SIGTERM, None)
            return PREEMPT_EXIT_CODE  # child saved interrupt.ch and exited

    sup = Supervisor(
        lambda i: FakeChild(),
        progress=lambda: 3,
        policy=RetryPolicy(max_restarts=5),
        sleep=lambda s: None,
    )
    holder["sup"] = sup
    res = sup.run()
    assert sent == [int(signal_mod.SIGTERM)]
    assert res.status == "terminated"
    assert len(res.attempts) == 1  # no restart after the forwarded signal
    assert res.exit_code == 128 + int(signal_mod.SIGTERM)
    assert "terminated by signal" in res.diagnosis


def test_build_child_argv_strips_and_repoints():
    argv = ["-c", "cfg", "--supervise", "--last", "stale.ch", "--n_epochs", "2"]
    assert build_child_argv(argv, resume="new.ch") == [
        "-c", "cfg", "--n_epochs", "2", "--last", "new.ch",
    ]
    # without a resume target, an explicit --last is the user's to keep
    assert build_child_argv(argv) == [
        "-c", "cfg", "--last", "stale.ch", "--n_epochs", "2",
    ]
    assert build_child_argv(["--supervise=true", "--last=x"], resume="y.ch") == [
        "--last", "y.ch",
    ]


# ---------------------------------------------------------------------------
# End-to-end chaos: real child processes through the real Supervisor
# ---------------------------------------------------------------------------

_CHILD_SCRIPT = textwrap.dedent(
    """
    import os, sys
    import numpy as np

    from ml_recipe_tpu.resilience import faults
    from ml_recipe_tpu.resilience.watchdog import Watchdog, install
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict, peek_global_step, save_state_dict_sharded,
    )

    ckpt = sys.argv[1]
    n_steps = int(sys.argv[2])

    wd_timeout = float(os.environ.get("WD_TIMEOUT", "0") or 0)
    wd = install(Watchdog(wd_timeout)) if wd_timeout else None

    params = {"w": np.zeros(4, dtype=np.float32)}
    start = 0
    if peek_global_step(ckpt) is not None:
        params, _, _, got = load_state_dict(ckpt, params=params)
        start = got or 0

    ctx = wd.watch("training run") if wd else None
    tick = ctx.__enter__() if ctx else (lambda *a: None)
    for step in range(start + 1, n_steps + 1):
        faults.fire("trainer.step")
        tick(f"step {step}")
        params = {"w": params["w"] + 1.0}
        save_state_dict_sharded(ckpt, params=params, global_step=step)
        if wd is not None:
            wd.note_progress(step)
    if ctx is not None:
        ctx.__exit__(None, None, None)
    print(f"DONE step={n_steps} w0={float(params['w'][0])}")
    """
)

_FAST_POLICY = RetryPolicy(
    max_restarts=3, backoff_base=0.01, backoff_max=0.02,
    crash_loop_window=2, seed=0,
)


def _run_supervised(tmp_path, run_tag, *, fault_plan, wd_timeout=None, n_steps=3):
    """One supervised run of the child script in a fresh directory; returns
    (result, final peeked step, collected child stderr)."""
    run_dir = tmp_path / run_tag
    run_dir.mkdir()
    script = run_dir / "child.py"
    script.write_text(_CHILD_SCRIPT)
    ckpt = str(run_dir / "state.ckpt")
    log = run_dir / "child.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULTS"] = fault_plan
    env["MLRT_FAULT_STATE"] = str(run_dir / "fault-state")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if wd_timeout is not None:
        env["WD_TIMEOUT"] = str(wd_timeout)

    def launch(attempt_i):
        fh = open(log, "ab")
        return subprocess.Popen(
            [sys.executable, str(script), ckpt, str(n_steps)],
            env=env, cwd=REPO_ROOT, stdout=fh, stderr=fh,
        )

    from ml_recipe_tpu.train.checkpoint import peek_global_step

    sup = Supervisor(
        launch,
        progress=lambda: peek_global_step(ckpt),
        policy=_FAST_POLICY,
        attempt_timeout=120,
        sleep=lambda s: None,
    )
    result = sup.run()
    return result, peek_global_step(ckpt), log.read_text(errors="replace")


def test_chaos_kill_between_shard_and_manifest(tmp_path):
    """Acceptance (a): a kill between shard-write and manifest-write leaves
    the previous checkpoint loadable; the supervisor resumes at its
    global_step and the run completes — identically on a second run."""
    from ml_recipe_tpu.train.checkpoint import load_state_dict_sharded

    summaries = []
    for tag in ("run1", "run2"):
        result, final_step, log = _run_supervised(
            tmp_path, tag, fault_plan="ckpt.pre_manifest:kill@2!once"
        )
        assert result.status == "clean"
        assert result.outcomes() == ["crash", "clean"]
        killed = result.attempts[0]
        assert killed.returncode == KILL_EXIT_CODE
        # the kill hit step 2's save: step 1's checkpoint survived and is
        # what the second attempt resumed from
        assert killed.step_after == 1
        assert result.attempts[1].step_before == 1
        assert final_step == 3
        # resumed values are continuous: w == n_steps proves the restart
        # loaded step 1's params rather than starting over
        p, _, _, _ = load_state_dict_sharded(
            str(tmp_path / tag / "state.ckpt"),
            params={"w": np.zeros(4, dtype=np.float32)},
        )
        assert float(p["w"][0]) == 3.0
        assert "FAULT: kill at ckpt.pre_manifest" in log
        summaries.append(
            (result.outcomes(), [a.returncode for a in result.attempts],
             [round(a.backoff, 9) for a in result.attempts])
        )
    assert summaries[0] == summaries[1], "chaos scenario must be deterministic"


def test_chaos_stall_trips_watchdog_and_recovers(tmp_path):
    """Acceptance (b): an injected step stall trips the watchdog (stack
    dump + abort with the hang exit code); the supervisor restarts and the
    run completes within the retry budget — deterministically."""
    summaries = []
    for tag in ("run1", "run2"):
        result, final_step, log = _run_supervised(
            tmp_path, tag,
            # stall >> timeout >> any legitimate step even on a loaded CI
            # machine: the drill must only ever trip on the injected stall
            fault_plan="trainer.step:stall@2~60!once",
            wd_timeout=3.0,
        )
        assert result.status == "clean"
        assert result.outcomes() == ["hang", "clean"]
        assert result.attempts[0].returncode == WATCHDOG_EXIT_CODE
        assert result.attempts[0].step_after == 1  # stalled at step 2
        assert final_step == 3
        assert "WATCHDOG" in log and "exceeded 3s" in log
        assert "last completed step: 1" in log
        # the dump names the stalled frame (time.sleep inside the fault)
        assert "Thread" in log or "thread" in log
        summaries.append((result.outcomes(), [a.returncode for a in result.attempts]))
    assert summaries[0] == summaries[1]


def test_chaos_crash_loop_aborts_with_diagnosis(tmp_path, capsys):
    """Acceptance (c): an unrecoverable crash-loop aborts after K attempts
    with a non-zero exit and a diagnosis line — not a burned retry budget."""
    summaries = []
    for tag in ("run1", "run2"):
        result, final_step, log = _run_supervised(
            tmp_path, tag, fault_plan="trainer.step:raise@1x*"
        )
        assert result.status == "crash-loop"
        assert result.exit_code != 0
        assert result.outcomes() == ["crash", "crash"]  # window == 2
        assert final_step is None  # never saved anything
        assert "crash-loop" in result.diagnosis
        assert "no global_step progress" in result.diagnosis
        assert "injected fault at trainer.step" in log
        summaries.append((result.outcomes(), [a.returncode for a in result.attempts]))
    assert summaries[0] == summaries[1]


# ---------------------------------------------------------------------------
# Async-checkpoint chaos (ISSUE 14): kill mid-persist on the background
# thread; the previous valid checkpoint must stay newest and the goodput
# ledger must still partition wall-clock exactly across the restart.
# ---------------------------------------------------------------------------

_ASYNC_CKPT_CHILD_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    import numpy as np

    from ml_recipe_tpu.metrics.goodput import GoodputLedger
    from ml_recipe_tpu.resilience.checkpoint_async import AsyncCheckpointer
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict, peek_global_step, persist_state, snapshot_state,
    )

    ckpt = sys.argv[1]
    n_steps = int(sys.argv[2])
    ledger_path = sys.argv[3]

    params = {"w": np.zeros(4, dtype=np.float32)}
    start = 0
    if peek_global_step(ckpt) is not None:
        params, _, _, got = load_state_dict(ckpt, params=params)
        start = got or 0

    ledger = GoodputLedger(ledger_path, flush_every=1)
    ledger.note_run_start(start + 1)

    ck = AsyncCheckpointer()
    for step in range(start + 1, n_steps + 1):
        t0 = time.perf_counter()
        params = {"w": params["w"] + 1.0}
        time.sleep(0.01)  # the 'productive' work of the step
        ledger.note_step(
            step, wall_s=time.perf_counter() - t0, compile=(step == start + 1)
        )
        t1 = time.perf_counter()
        snap = snapshot_state(params=params, global_step=step, copy=True)
        ledger.note_checkpoint("save", time.perf_counter() - t1)
        ck.submit(
            ckpt, lambda s=snap: persist_state(ckpt, s),
            on_done=lambda secs, stalled: ledger.note_checkpoint(
                "save", max(0.0, secs - stalled), overlapped=True
            ),
        )
        time.sleep(0.005)  # the compute the persist overlaps with
        # completion barrier per step: the injected kill fires INSIDE
        # persist_state on the BACKGROUND thread (checkpoint.persist
        # site), so waiting here pins the crash to a deterministic
        # mid-persist window while the main thread is parked
        ck.wait()
    ledger.note_run_end(n_steps)
    print(f"DONE step={n_steps} w0={float(params['w'][0])}")
    """
)


def test_chaos_kill_mid_async_persist(tmp_path):
    """ISSUE-14 acceptance: a kill during the async save's background
    persist (``checkpoint.persist:kill@2!once`` — step 2's persist) must
    leave step 1's checkpoint as the newest valid one; the supervisor
    resumes from it and the run completes; the goodput ledger — attempt
    boundaries appended by the supervisor, step/checkpoint events by the
    child — still partitions total wall-clock exactly, with nonzero
    restart downtime, recompute, AND overlapped-persist accounting."""
    from ml_recipe_tpu.metrics.goodput import (
        BADPUT_CATEGORIES,
        read_ledger,
        summarize_events,
    )
    from ml_recipe_tpu.train.checkpoint import load_state_dict, peek_global_step

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    script = run_dir / "child.py"
    script.write_text(_ASYNC_CKPT_CHILD_SCRIPT)
    ckpt = str(run_dir / "state.ch")
    ledger_path = str(run_dir / "goodput.jsonl")
    log = run_dir / "child.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULTS"] = "checkpoint.persist:kill@2!once"
    env["MLRT_FAULT_STATE"] = str(run_dir / "fault-state")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")

    def launch(attempt_i):
        fh = open(log, "ab")
        return subprocess.Popen(
            [sys.executable, str(script), ckpt, "3", ledger_path],
            env=env, cwd=REPO_ROOT, stdout=fh, stderr=fh,
        )

    sup = Supervisor(
        launch,
        progress=lambda: peek_global_step(ckpt),
        policy=_FAST_POLICY,
        attempt_timeout=120,
        sleep=lambda s: None,
        ledger_path=ledger_path,
    )
    result = sup.run()

    assert result.status == "clean"
    assert result.outcomes() == ["crash", "clean"]
    killed = result.attempts[0]
    assert killed.returncode == KILL_EXIT_CODE
    # the kill hit step 2's PERSIST: step 1's checkpoint survived as the
    # newest valid one and is what the second attempt resumed from
    assert killed.step_after == 1
    assert result.attempts[1].step_before == 1
    assert peek_global_step(ckpt) == 3
    p, _, _, _ = load_state_dict(
        ckpt, params={"w": np.zeros(4, dtype=np.float32)}
    )
    assert float(p["w"][0]) == 3.0
    assert "FAULT: kill at checkpoint.persist" in log.read_text(
        errors="replace"
    )

    # ledger partition exactness across the crash + resume
    s = summarize_events(read_ledger(ledger_path))
    assert s["attempts"] == 2
    total = s["total_wall_s"]
    accounted = s["productive_s"] + sum(
        s["badput_s"][c] for c in BADPUT_CATEGORIES
    )
    assert accounted == pytest.approx(total, rel=1e-9, abs=1e-9)
    assert s["badput_s"]["restart_downtime"] > 0
    # step 2 ran in attempt 1, was lost mid-persist and replayed: the
    # resume's run_start reclassifies its productive time as recompute
    assert s["recomputed_steps"] >= 1
    assert s["badput_s"]["recompute"] > 0
    assert s["badput_s"]["checkpoint_save"] > 0       # blocking snapshots
    assert s["checkpoint_overlapped_s"] > 0           # background persists
    # overlapped persist time is OUTSIDE the badput partition (it ran
    # under training) — the exactness assert above already proved it was
    # not double-booked


# ---------------------------------------------------------------------------
# Warm-pool chaos (ISSUE 17): supervised kill -> resume where the resumed
# attempt deserializes its program from the AOT store instead of compiling.
# ---------------------------------------------------------------------------

_AOT_WARM_CHILD_SCRIPT = textwrap.dedent(
    """
    import sys, time
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.metrics.goodput import GoodputLedger
    from ml_recipe_tpu.ops import aot
    from ml_recipe_tpu.resilience import faults
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict, peek_global_step, save_state_dict_sharded,
    )

    ckpt = sys.argv[1]
    n_steps = int(sys.argv[2])
    ledger_path = sys.argv[3]

    params = {"w": np.zeros((16, 16), dtype=np.float32)}
    start = 0
    if peek_global_step(ckpt) is not None:
        params, _, _, got = load_state_dict(ckpt, params=params)
        start = got or 0

    ledger = GoodputLedger(ledger_path, flush_every=1)
    ledger.note_run_start(start + 1)

    def loss(w, x):
        h = jnp.tanh(x @ w)
        return jnp.sum(jnp.tanh(h @ w) ** 2)

    def step_fn(w, x):
        return w - 0.01 * jax.grad(loss)(w, x)

    x = jnp.ones((16, 16), dtype=jnp.float32)
    store = aot.get()
    t0 = time.perf_counter()
    program = store.load_or_compile(
        "chaos-step", jax.jit(step_fn), jnp.asarray(params["w"]), x,
        geometry="16x16", plan="data1",
    )
    build_s = time.perf_counter() - t0
    # per-attempt tally: the resumed attempt's event must show misses == 0
    ledger.note_aot(store.hits, store.misses, sum(store.load_times_s))

    w = jnp.asarray(params["w"])
    for step in range(start + 1, n_steps + 1):
        t0 = time.perf_counter()
        faults.fire("trainer.step")
        w = program(w, x)
        np.asarray(w)
        first = step == start + 1
        ledger.note_step(
            step,
            wall_s=(time.perf_counter() - t0) + (build_s if first else 0.0),
            compile=first,
            aot_hit=(store.misses == 0) if first else None,
        )
        save_state_dict_sharded(
            ckpt, params={"w": np.asarray(w)}, global_step=step
        )
    ledger.note_run_end(n_steps)
    print(f"DONE step={n_steps}")
    """
)


def test_chaos_warm_pool_restart_is_zero_compile(tmp_path):
    """ISSUE-17 acceptance: kill a supervised attempt after its first step
    and let the supervisor resume. The replacement attempt must perform
    ZERO XLA compiles — its ledger ``aot`` event shows ``misses == 0`` —
    its compile_warmup window must be the artifact-load time (a fraction
    of the cold attempt's real compile), and the goodput partition must
    stay exact across the crash."""
    from ml_recipe_tpu.metrics.goodput import (
        BADPUT_CATEGORIES,
        read_ledger,
        summarize_events,
    )
    from ml_recipe_tpu.train.checkpoint import peek_global_step

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    script = run_dir / "child.py"
    script.write_text(_AOT_WARM_CHILD_SCRIPT)
    ckpt = str(run_dir / "state.ckpt")
    ledger_path = str(run_dir / "goodput.jsonl")
    log = run_dir / "child.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULTS"] = "trainer.step:kill@2!once"
    env["MLRT_FAULT_STATE"] = str(run_dir / "fault-state")
    # a dedicated store dir shared ONLY by this drill's attempts, and a
    # fresh XLA compile cache so attempt 1's compile is genuinely cold —
    # the cold-vs-warm compile_warmup comparison below depends on both
    env["MLRT_AOT_CACHE"] = str(run_dir / "aot")
    env["JAX_COMPILATION_CACHE_DIR"] = str(run_dir / "xla-cache")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")

    def launch(attempt_i):
        fh = open(log, "ab")
        return subprocess.Popen(
            [sys.executable, str(script), ckpt, "3", ledger_path],
            env=env, cwd=REPO_ROOT, stdout=fh, stderr=fh,
        )

    sup = Supervisor(
        launch,
        progress=lambda: peek_global_step(ckpt),
        policy=_FAST_POLICY,
        attempt_timeout=120,
        sleep=lambda s: None,
        ledger_path=ledger_path,
    )
    result = sup.run()

    assert result.status == "clean", log.read_text(errors="replace")
    assert result.outcomes() == ["crash", "clean"]
    assert result.attempts[0].returncode == KILL_EXIT_CODE
    assert result.attempts[0].step_after == 1  # killed at step 2
    assert peek_global_step(ckpt) == 3

    # split the ledger at attempt boundaries: the events each child wrote
    # after ITS run_start are that attempt's
    events = sorted(
        (e for e in read_ledger(ledger_path) if "t" in e),
        key=lambda e: e["t"],
    )
    attempts, current = [], None
    for e in events:
        if e.get("ev") == "run_start":
            current = []
            attempts.append(current)
        elif current is not None:
            current.append(e)
    assert len(attempts) == 2

    cold_aot = next(e for e in attempts[0] if e["ev"] == "aot")
    warm_aot = next(e for e in attempts[1] if e["ev"] == "aot")
    assert cold_aot["misses"] == 1 and cold_aot["hits"] == 0
    # THE acceptance: the resumed attempt compiled nothing
    assert warm_aot["misses"] == 0 and warm_aot["hits"] == 1
    assert warm_aot["load_s"] > 0

    # the cold attempt's first-step window booked a real XLA compile; the
    # warm attempt's booked an artifact load — flagged and far smaller
    cold_win = next(e for e in attempts[0] if e["ev"] == "steps")
    warm_win = next(e for e in attempts[1] if e["ev"] == "steps")
    assert cold_win["aot_hit"] is False
    assert warm_win["aot_hit"] is True
    assert cold_win["compile_s"] > 0
    assert warm_win["compile_s"] < cold_win["compile_s"]

    # partition exactness across the crash + zero-compile resume
    s = summarize_events(events)
    assert s["attempts"] == 2
    assert s["aot_hits"] == 1 and s["aot_misses"] == 1
    accounted = s["productive_s"] + sum(
        s["badput_s"][c] for c in BADPUT_CATEGORIES
    )
    assert accounted == pytest.approx(s["total_wall_s"], rel=1e-9, abs=1e-9)
    assert s["badput_s"]["restart_downtime"] > 0


# ---------------------------------------------------------------------------
# Full CLI drill (slow tier): --supervise end-to-end through cli.train
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cli_supervise_recovers_from_checkpoint_kill(tmp_path):
    """`train --supervise` with a one-shot kill during the epoch-end
    checkpoint save: attempt 1 dies mid-save, attempt 2 reruns to a clean
    finish — the whole loop through the real CLI entry point."""
    from helpers import make_tokenizer, nq_line, write_corpus

    make_tokenizer(tmp_path)
    corpus = write_corpus(tmp_path, [nq_line(example_id=str(i)) for i in range(8)])
    cfg = tmp_path / "sup.cfg"
    cfg.write_text(
        "\n".join(
            [
                "model=bert-tiny",
                f"vocab_file={tmp_path / 'vocab.txt'}",
                f"data_path={corpus}",
                f"processed_data_path={tmp_path / 'processed'}",
                f"dump_dir={tmp_path / 'results'}",
                "experiment_name=sup",
                "max_seq_len=64",
                "max_question_len=16",
                "doc_stride=16",
                "n_epochs=1",
                "train_batch_size=8",
                "test_batch_size=8",
                "n_jobs=2",
                "seed=0",
            ]
        )
        + "\n"
    )

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULT_STATE"] = str(tmp_path / "fault-state")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "ml_recipe_tpu.cli.train",
            "-c", str(cfg),
            "--supervise",
            "--max_restarts", "2",
            "--backoff_base", "0.01",
            "--backoff_max", "0.02",
            "--fault_plan", "ckpt.pre_write:kill@1!once",
        ],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert (tmp_path / "results" / "sup" / "last.ch").exists()
    assert "FAULT: kill at ckpt.pre_write" in proc.stderr


# ---------------------------------------------------------------------------
# Tooling: the bare-except lint gate
# ---------------------------------------------------------------------------


def test_no_bare_except_in_package():
    script = os.path.join(REPO_ROOT, "scripts", "check_bare_except.sh")
    proc = subprocess.run(
        ["bash", script], capture_output=True, text=True, cwd=REPO_ROOT
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# ZeRO-1 checkpoint portability (ISSUE 8): manifest shard layout + resume
# across a mesh-shape change
# ---------------------------------------------------------------------------


def test_manifest_records_shard_layout_and_peek(tmp_path):
    """The sharded manifest records each leaf's shard count (and the
    headline ``shards: N``), readable WITHOUT loading tensors; a restore
    into a mismatched optimizer layout fails loudly with expected-vs-found
    instead of a shape error mid-restore."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flax import serialization
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.train.checkpoint import (
        CheckpointLayoutError,
        load_state_dict_sharded,
        peek_checkpoint_layout,
        save_state_dict_sharded,
    )

    mesh = build_mesh("data:8")
    params = {"w": np.arange(16, dtype=np.float32)}
    opt = {
        "mu": {
            "w": jax.device_put(
                np.ones(16, np.float32), NamedSharding(mesh, P("data"))
            )
        },
        "count": np.int32(3),
    }
    ckpt = tmp_path / "zero.ch"
    save_state_dict_sharded(
        str(ckpt), params=params, opt_state=opt, global_step=7,
        extra={"opt_sharding": "zero1"},
    )

    manifest = serialization.msgpack_restore(
        (ckpt / "manifest.msgpack").read_bytes()
    )
    assert manifest["shards"] == 8
    assert manifest["groups"]["optimizer"]["mu/w"]["shards"] == 8
    assert manifest["groups"]["model"]["w"]["shards"] == 1

    layout = peek_checkpoint_layout(ckpt)
    assert layout == {
        "format": "sharded",
        "global_step": 7,
        "process_count": 1,
        "shards": 8,
        "opt_sharding": "zero1",
        # written without a trainer: no ParallelPlan topology was recorded
        # (trainer saves stamp plan.describe() here — ISSUE-15), nor a
        # pipeline schedule/layout (ISSUE-19: trainer pipe saves stamp both)
        "mesh_axes": None,
        "pipe_schedule": None,
        "pipe_param_layout": None,
        "groups": {"model": 1, "optimizer": 2},
    }
    assert peek_checkpoint_layout(tmp_path / "absent.ch") is None

    # loud expected-vs-found on a mismatched optimizer layout (a different
    # chain), BEFORE any tensor restore
    bad_target = {"nu": {"w": np.zeros(16, np.float32)}, "count": np.int32(0)}
    with pytest.raises(CheckpointLayoutError) as err:
        load_state_dict_sharded(
            str(ckpt), params=params, opt_state=bad_target
        )
    msg = str(err.value)
    assert "mu/w" in msg and "nu/w" in msg and "shards=8" in msg

    # equal-rank shape changes are NOT a layout error — that is what a
    # ZeRO-1 mesh-shape change looks like (the trainer crops/zero-fills)
    wider = {"mu": {"w": np.zeros(24, np.float32)}, "count": np.int32(0)}
    restored = load_state_dict_sharded(
        str(ckpt), params=params, opt_state=wider
    )
    assert restored[3] == 7

    # rank changes ARE a layout error, not a cryptic numpy failure
    bad_rank = {"mu": {"w": np.zeros((4, 4), np.float32)}, "count": np.int32(0)}
    with pytest.raises(CheckpointLayoutError, match="rank mismatch"):
        load_state_dict_sharded(str(ckpt), params=params, opt_state=bad_rank)


_ZERO_RESHAPE_TRAIN = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, sys.argv[4])                    # tests/ (conftest)
    sys.path.insert(0, os.path.dirname(sys.argv[4]))   # repo root
    import conftest  # 8-device CPU mesh + autotune cache isolation
    import pathlib
    import numpy as np
    import jax

    from test_trainer import _make_trainer
    from ml_recipe_tpu.parallel.sharding import gather_to_host

    work = pathlib.Path(sys.argv[1]); mesh_spec = sys.argv[2]
    mode = sys.argv[3]
    (work / mesh_spec.replace(":", "_")).mkdir(exist_ok=True)
    kw = {}
    if mode != "off":
        # the zero1 phases save asynchronously: an async save must not
        # change what a cross-mesh restore sees
        kw = dict(optimizer_sharding=mode, zero_min_size=0,
                  async_checkpoint=True)
    t, _ = _make_trainer(
        work / mesh_spec.replace(":", "_"), mesh_spec=mesh_spec,
        dropout=0.0, n_epochs=1, batch_split=2, sharded_checkpoint=True,
        **kw,
    )
    ckpt = work / "zero_reshape.ch"
    if ckpt.exists():
        t.load_state_dict(ckpt)
        resumed_from = t.global_step
        assert resumed_from > 0, "resume did not restore the step"
        # params must equal what the saver trained, bit for bit on host
        want = np.load(work / "params_checksum.npy")
        leaves = jax.tree_util.tree_leaves(gather_to_host(t.params))
        got = np.float64(sum(np.asarray(l, np.float64).sum() for l in leaves))
        assert abs(got - want) < 1e-6, (got, want)
        # optimizer moments survive too (logical overlap; padding differs
        # with the mesh) — then training CONTINUES on the new mesh
        t.n_epochs = 1
        t.train()
        assert t.global_step > resumed_from
        print(f"RESUMED_OK mesh={mesh_spec} mode={mode} "
              f"step={t.global_step}", flush=True)
    else:
        t.train()
        t.save_state_dict(ckpt)
        t.finish_pending_checkpoint()  # async save must land before exit
        leaves = jax.tree_util.tree_leaves(gather_to_host(t.params))
        total = np.float64(sum(np.asarray(l, np.float64).sum() for l in leaves))
        np.save(work / "params_checksum.npy", total)
        print(f"SAVED_OK step={t.global_step}", flush=True)
    """
)


@pytest.mark.slow
def test_zero1_checkpoint_survives_mesh_reshape(tmp_path):
    """ISSUE-8 acceptance: a checkpoint saved under zero1 at mesh N=4
    restores at mesh M=2 (and under --optimizer_sharding off at N=8) with
    crc32 manifest verification passing, and training continues. Each
    phase runs in its own process — the same process-per-topology shape a
    real resize takes (and XLA CPU corrupts its heap when a second mesh
    trains after a cross-mesh load in one process)."""
    script = tmp_path / "phase.py"
    script.write_text(_ZERO_RESHAPE_TRAIN)
    tests_dir = os.path.dirname(os.path.abspath(__file__))

    def phase(mesh_spec, mode):
        proc = subprocess.run(
            [sys.executable, str(script), str(tmp_path), mesh_spec, mode,
             tests_dir],
            capture_output=True, text=True, timeout=900,
            cwd=tests_dir,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
        return proc.stdout

    out = phase("data:4", "zero1")
    assert "SAVED_OK" in out

    from ml_recipe_tpu.train.checkpoint import peek_checkpoint_layout

    layout = peek_checkpoint_layout(tmp_path / "zero_reshape.ch")
    assert layout["shards"] == 4 and layout["opt_sharding"] == "zero1"
    # the manifest records the saver's declarative plan (ISSUE-15)
    assert layout["mesh_axes"] == {"data": 4}

    # shrink: N=4 -> M=2, still zero1
    assert "RESUMED_OK mesh=data:2 mode=zero1" in phase("data:2", "zero1")
    # ISSUE-15 reshard drill: restore the data:4 save onto a PIPELINE-
    # bearing plan (data:2,pipe:2) — the zero1 state crops/zero-fills
    # onto the new data-axis padding and training continues on the GPipe
    # schedule
    assert "RESUMED_OK mesh=data:2,pipe:2 mode=zero1" in phase(
        "data:2,pipe:2", "zero1"
    )
    # and back to a replicated layout on a wider mesh
    assert "RESUMED_OK mesh=data:8 mode=off" in phase("data:8", "off")


_PIPE_STAGE_RESHAPE_TRAIN = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, sys.argv[5])                    # tests/ (conftest)
    sys.path.insert(0, os.path.dirname(sys.argv[5]))   # repo root
    import conftest  # 8-device CPU mesh + autotune cache isolation
    import pathlib
    import numpy as np
    import jax

    from test_trainer import _make_trainer
    from ml_recipe_tpu.parallel.sharding import gather_to_host

    work = pathlib.Path(sys.argv[1]); mesh_spec = sys.argv[2]
    schedule = sys.argv[3]; out_tag = sys.argv[4]
    tag = mesh_spec.replace(":", "_").replace(",", "__") + "_" + out_tag
    (work / tag).mkdir(exist_ok=True)
    kw = dict(optimizer_sharding="zero1", zero_min_size=0,
              sharded_checkpoint=True)
    if "pipe" in mesh_spec:
        kw["pipe_schedule"] = schedule
    t, _ = _make_trainer(
        work / tag, mesh_spec=mesh_spec, dropout=0.0, n_epochs=1,
        batch_split=2, **kw,
    )
    ckpt = work / "pipe_stage.ch"
    if ckpt.exists():
        t.load_state_dict(ckpt)
        resumed_from = t.global_step
        assert resumed_from > 0, "resume did not restore the step"
        # a STAGE-SHARDED save must restore bit-for-bit on host, whatever
        # the live layout (wider data axis / other schedule / no pipe)
        want = np.load(work / "params_checksum.npy")
        leaves = jax.tree_util.tree_leaves(gather_to_host(t.params))
        got = np.float64(sum(np.asarray(l, np.float64).sum() for l in leaves))
        assert abs(got - want) < 1e-6, (got, want)
        t.n_epochs = 1
        t.train()
        assert t.global_step > resumed_from
        final = gather_to_host(t.params)
        flat = {}
        def _walk(tree, prefix=""):
            for k, v in tree.items():
                key = prefix + "/" + str(k) if prefix else str(k)
                if isinstance(v, dict):
                    _walk(v, key)
                else:
                    flat[key] = np.asarray(v)
        _walk(final)
        np.savez(work / ("final_" + out_tag + ".npz"), **flat)
        print(f"RESUMED_OK mesh={mesh_spec} schedule={schedule} "
              f"step={t.global_step}", flush=True)
    else:
        from ml_recipe_tpu.train.checkpoint import peek_checkpoint_layout
        t.train()
        t.save_state_dict(ckpt)
        leaves = jax.tree_util.tree_leaves(gather_to_host(t.params))
        total = np.float64(sum(np.asarray(l, np.float64).sum() for l in leaves))
        np.save(work / "params_checksum.npy", total)
        layout = peek_checkpoint_layout(ckpt)
        assert layout["pipe_schedule"] == schedule, layout
        assert layout["pipe_param_layout"] == "stage", layout
        print(f"SAVED_OK step={t.global_step}", flush=True)
    """
)


@pytest.mark.slow
def test_pipe_stage_checkpoint_reshape_and_schedule_flip(tmp_path):
    """ISSUE-19 acceptance drill: a STAGE-SHARDED save at ``data:2,pipe:2``
    (trunk leaves over pipe x data) restores onto a pipe-less ``data:4``
    plan bit-for-bit, and a gpipe save resumes under ``--pipe_schedule
    1f1b`` — the continued trajectories of the two schedules agree within
    the PR-15 pipeline tolerance (identical data order; the schedules
    reorder the same microbatch work). Process-per-topology like the
    zero-reshape drill."""
    script = tmp_path / "phase.py"
    script.write_text(_PIPE_STAGE_RESHAPE_TRAIN)
    tests_dir = os.path.dirname(os.path.abspath(__file__))

    def phase(mesh_spec, schedule, out_tag):
        proc = subprocess.run(
            [sys.executable, str(script), str(tmp_path), mesh_spec,
             schedule, out_tag, tests_dir],
            capture_output=True, text=True, timeout=900,
            cwd=tests_dir,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
        return proc.stdout

    # save under gpipe with stage-local trunk storage
    out = phase("data:2,pipe:2", "gpipe", "save")
    assert "SAVED_OK" in out

    from ml_recipe_tpu.train.checkpoint import peek_checkpoint_layout

    layout = peek_checkpoint_layout(tmp_path / "pipe_stage.ch")
    assert layout["mesh_axes"] == {"data": 2, "pipe": 2}
    assert layout["pipe_schedule"] == "gpipe"
    assert layout["pipe_param_layout"] == "stage"
    # widest leaf shards pipe x data ways
    assert layout["shards"] == 4

    # stage-sharded save -> pipe-less wider data axis
    assert "RESUMED_OK mesh=data:4" in phase("data:4", "gpipe", "data4")
    # schedule-flip resume: same mesh, gpipe save -> 1f1b continuation
    assert "RESUMED_OK mesh=data:2,pipe:2 schedule=1f1b" in phase(
        "data:2,pipe:2", "1f1b", "flip1f1b"
    )
    # reference continuation under the saved schedule
    assert "RESUMED_OK mesh=data:2,pipe:2 schedule=gpipe" in phase(
        "data:2,pipe:2", "gpipe", "flipgpipe"
    )
    ref = np.load(tmp_path / "final_flipgpipe.npz")
    got = np.load(tmp_path / "final_flip1f1b.npz")
    assert set(ref.files) == set(got.files)
    for k in ref.files:
        np.testing.assert_allclose(
            got[k], ref[k], rtol=1e-4, atol=1e-5,
            err_msg=f"schedule-flip trajectory diverged at {k}",
        )


# ---------------------------------------------------------------------------
# Elastic coordination plane (ISSUE 16): guarded reads, schema versioning
# ---------------------------------------------------------------------------


def test_read_coordination_json_absent_is_immediate(tmp_path):
    """Absence is a protocol state (a host that has not published yet),
    not an error to retry: no sleeps, None now."""
    from ml_recipe_tpu.resilience.coordination import read_coordination_json

    sleeps = []
    got = read_coordination_json(
        tmp_path / "host-001.json", sleep=sleeps.append
    )
    assert got is None and sleeps == []


def test_read_coordination_json_retries_torn_read(tmp_path):
    """A torn document (shared-FS mid-replace window) heals within the
    retry budget: the doc comes back, never a spurious 'absent'."""
    from ml_recipe_tpu.resilience.coordination import (
        COORD_SCHEMA_VERSION, read_coordination_json,
    )

    path = tmp_path / "host-001.json"
    path.write_text('{"schema": 1, "status": "runn')  # mid-replace torn
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        path.write_text(
            '{"schema": %d, "status": "running"}' % COORD_SCHEMA_VERSION
        )

    got = read_coordination_json(path, sleep=sleep)
    assert got == {"schema": COORD_SCHEMA_VERSION, "status": "running"}
    assert len(sleeps) == 1
    # backoff grows when the tear persists longer
    path.write_text("garbage")
    delays = []

    def sleep2(s):
        delays.append(s)
        if len(delays) == 2:
            path.write_text('{"schema": %d}' % COORD_SCHEMA_VERSION)

    assert read_coordination_json(path, sleep=sleep2) is not None
    assert delays == [0.05, 0.1]


def test_read_coordination_json_degrades_after_budget(tmp_path):
    """Persistent garbage degrades to None (treated as absent) after the
    bounded budget — never an exception into the supervision loop."""
    from ml_recipe_tpu.resilience.coordination import read_coordination_json

    path = tmp_path / "host-001.json"
    path.write_text("not json at all")
    sleeps = []
    assert read_coordination_json(path, retries=2, sleep=sleeps.append) is None
    assert len(sleeps) == 2  # retries, then gave up on the final attempt


def test_read_coordination_json_rejects_schema_mismatch(tmp_path):
    """A document from an incompatible build fails LOUDLY at first read —
    a pod where half the hosts run an older sidecar format must not
    half-coordinate."""
    from ml_recipe_tpu.resilience.coordination import (
        CoordinationSchemaError, read_coordination_json,
    )

    path = tmp_path / "host-001.json"
    path.write_text('{"schema": 0, "status": "running"}')
    with pytest.raises(CoordinationSchemaError, match="schema 0"):
        read_coordination_json(path)
    path.write_text('{"status": "running"}')  # pre-versioning build
    with pytest.raises(CoordinationSchemaError, match="schema None"):
        read_coordination_json(path)
    # a non-object document is noise, not a protocol statement
    path.write_text('[1, 2, 3]')
    assert read_coordination_json(path) is None


def test_supervisor_sidecar_schema_roundtrip(tmp_path):
    """write_supervisor_state stamps the schema; peek reads it back, and
    rejects (as None, loudly logged) a sidecar from an older build."""
    from ml_recipe_tpu.resilience.coordination import COORD_SCHEMA_VERSION
    from ml_recipe_tpu.resilience.supervisor import (
        peek_supervisor_state, write_supervisor_state,
    )

    path = tmp_path / "supervisor_state.json"
    write_supervisor_state(path, {"status": "running", "attempts": 1})
    doc = peek_supervisor_state(path)
    assert doc["status"] == "running"
    assert doc["schema"] == COORD_SCHEMA_VERSION
    path.write_text('{"status": "running"}')  # schema-less old sidecar
    assert peek_supervisor_state(path) is None


def test_pod_coordinator_publish_and_peer_views(tmp_path):
    """Two coordinators on one directory see each other's documents; the
    child-side heartbeat (watchdog-wired in production) surfaces through
    child_step."""
    from ml_recipe_tpu.resilience.coordination import (
        PodCoordinator, write_child_heartbeat,
    )

    coord_dir = tmp_path / "pod"
    a = PodCoordinator(coord_dir, host=0, n_hosts=2)
    b = PodCoordinator(coord_dir, host=1, n_hosts=2)
    a.publish("running", generation=2, attempt=1, live_hosts=[0, 1])
    b.publish("restarting", generation=3, attempt=4)

    seen_by_b = b.peer_states()
    assert set(seen_by_b) == {0}
    assert seen_by_b[0]["status"] == "running"
    assert seen_by_b[0]["generation"] == 2
    assert seen_by_b[0]["live_hosts"] == [0, 1]
    assert a.peer_state(1)["status"] == "restarting"

    assert a.child_step(1) is None  # no child ever beat
    write_child_heartbeat(coord_dir, 1, step=17)
    assert a.child_step(1) == 17


# ---------------------------------------------------------------------------
# Host-scoped fault specs (%hostN): multi-host chaos determinism
# ---------------------------------------------------------------------------


def test_fault_plan_host_scope_grammar():
    plan = FaultPlan.parse("trainer.step:kill@4%host1; loader.read:raise@2")
    assert [(s.site, s.kind, s.hit, s.host) for s in plan.specs] == [
        ("trainer.step", "kill", 4, 1),
        ("loader.read", "raise", 2, None),
    ]
    # scope composes with the rest of the grammar
    spec = FaultPlan.parse("loader.read:raise@1x3!once%host0").specs[0]
    assert (spec.count, spec.once, spec.host) == (3, True, 0)


@pytest.mark.parametrize(
    "bad",
    ["trainer.step:kill%h1", "trainer.step:kill%host",
     "loader.read:raise%pod1"],
)
def test_fault_plan_rejects_malformed_host_scope(bad):
    with pytest.raises(ValueError, match="host scope|malformed"):
        FaultPlan.parse(bad)


def test_fault_host_scope_gates_action_not_counter(monkeypatch):
    """The ARRIVAL counter advances on every host (the nth step is the
    nth step everywhere); only the ACTION is scoped — that is what makes
    'kill host 1 at step 4' mean the same step on every host."""
    from ml_recipe_tpu.resilience.faults import HOST_ENV

    monkeypatch.setenv(HOST_ENV, "0")
    plan = FaultPlan.parse("loader.read:raise@1%host1")
    plan.fire("loader.read")  # scoped to host 1: no action on host 0
    assert plan.hits("loader.read") == 1

    monkeypatch.setenv(HOST_ENV, "1")
    plan2 = FaultPlan.parse("loader.read:raise@1%host1")
    with pytest.raises(FaultError):
        plan2.fire("loader.read")


def test_fault_once_markers_are_per_host(tmp_path, monkeypatch):
    """!once state is keyed per host: a SHARED state dir (the normal
    multi-host layout) must never let host 0's firing suppress host 1's."""
    from ml_recipe_tpu.resilience.faults import HOST_ENV

    state = str(tmp_path / "fault-state")
    spec = "loader.read:raise@1!once"

    monkeypatch.setenv(HOST_ENV, "0")
    with pytest.raises(FaultError):
        FaultPlan.parse(spec, state_dir=state).fire("loader.read")
    # host 0 restarted: suppressed by its own marker
    FaultPlan.parse(spec, state_dir=state).fire("loader.read")

    monkeypatch.setenv(HOST_ENV, "1")  # host 1, same state dir: still fires
    with pytest.raises(FaultError):
        FaultPlan.parse(spec, state_dir=state).fire("loader.read")


def test_current_host_defaults_and_ignores_garbage(monkeypatch):
    from ml_recipe_tpu.resilience.faults import HOST_ENV, current_host

    monkeypatch.delenv(HOST_ENV, raising=False)
    assert current_host() == 0
    monkeypatch.setenv(HOST_ENV, "3")
    assert current_host() == 3
    monkeypatch.setenv(HOST_ENV, "not-a-host")
    assert current_host() == 0


# ---------------------------------------------------------------------------
# ElasticSupervisor unit: scripted children + hand-written peer documents
# ---------------------------------------------------------------------------


def _write_peer(coord_dir, host, *, status="running", generation=0,
                age=0.0, step=None):
    """A peer host's coordination document, optionally back-dated by
    ``age`` seconds (the staleness signal)."""
    from ml_recipe_tpu.metrics.artifacts import atomic_write_json, wall_now
    from ml_recipe_tpu.resilience.coordination import COORD_SCHEMA_VERSION

    atomic_write_json(
        os.path.join(str(coord_dir), f"host-{host:03d}.json"),
        {
            "schema": COORD_SCHEMA_VERSION, "host": host, "pid": 0,
            "status": status, "generation": generation, "attempt": 0,
            "step": step, "exit_class": None, "live_hosts": None,
            "heartbeat": wall_now() - age,
        },
    )


def _elastic_supervisor(tmp_path, children, steps, *, host=0, n_hosts=2,
                        min_world=1, host_timeout=60.0, ledger=False,
                        flight=False):
    from ml_recipe_tpu.resilience.coordination import PodCoordinator
    from ml_recipe_tpu.resilience.supervisor import ElasticSupervisor

    coord = PodCoordinator(tmp_path / "pod", host=host, n_hosts=n_hosts)
    child_iter = iter(children)
    step_iter = iter(steps)
    return ElasticSupervisor(
        lambda i: next(child_iter),
        coordinator=coord,
        host_timeout=host_timeout,
        poll_interval=0.01,
        min_world=min_world,
        progress=lambda: next(step_iter),
        # max_restarts=0: ANY budget-charged restart would end the loop,
        # so a run that continues past a coordinated outcome proves the
        # exemption
        policy=RetryPolicy(max_restarts=0, crash_loop_window=10),
        sleep=lambda s: None,
        ledger_path=str(tmp_path / "goodput.jsonl") if ledger else None,
        flight_dir=str(tmp_path) if flight else None,
    )


def test_elastic_peer_generation_bump_is_pod_restart(tmp_path):
    """A peer at a higher generation means the pod is restarting: the
    outcome is pod-restart (budget-exempt, streak-exempt) and the
    generation is adopted."""
    from ml_recipe_tpu.resilience.coordination import read_coordination_json

    _write_peer(tmp_path / "pod", 1, generation=3)
    sup = _elastic_supervisor(tmp_path, [1, 0], [None, None, None, 1])
    res = sup.run()
    assert res.status == "clean"
    # rc 1 would classify as 'crash'; the coordination sweep overrides it
    assert res.outcomes() == ["pod-restart", "clean"]
    assert res.exit_code == 0
    assert sup.generation == 3
    # no host was lost: the peer is restarting, not dead
    assert sup.live_hosts() == [0, 1]
    own = read_coordination_json(tmp_path / "pod" / "host-000.json")
    assert own["status"] == "done" and own["generation"] == 3


def test_elastic_stale_heartbeat_is_host_lost(tmp_path):
    """A silently stale peer heartbeat is a DEAD HOST: the world shrinks,
    the generation bumps, and the ledger/flight record name the cause."""
    from ml_recipe_tpu.metrics.flightrec import newest_flight_record
    from ml_recipe_tpu.metrics.goodput import read_ledger, summarize_events

    from ml_recipe_tpu.resilience.coordination import write_child_heartbeat

    _write_peer(tmp_path / "pod", 1, age=120.0, step=41)
    write_child_heartbeat(tmp_path / "pod", 1, step=41)
    sup = _elastic_supervisor(
        tmp_path, [1, 0], [None, None, None, 7],
        host_timeout=5.0, ledger=True, flight=True,
    )
    res = sup.run()
    assert res.status == "clean"
    assert res.outcomes() == ["host-lost", "clean"]
    assert sup.live_hosts() == [0]
    assert sup.generation == 1
    assert "host death" in sup._lost_why[1]
    assert sup.world == {"hosts": [0], "size": 1, "rank": 0, "generation": 1}

    events = read_ledger(tmp_path / "goodput.jsonl")
    lost = [e for e in events if e.get("ev") == "host_lost"]
    assert len(lost) == 1
    assert lost[0]["lost"] == 1 and lost[0]["last_step"] == 41
    assert summarize_events(events)["hosts_lost"] == 1

    _, doc = newest_flight_record(tmp_path)
    assert "host_lost" in [e["kind"] for e in doc["events"]]


def test_elastic_peer_failed_status_is_classified_crash_loop(tmp_path):
    """A peer that PUBLISHED 'failed' (its own supervisor aborted) is a
    classified crash-loop, not a silent host death — the world shrinks
    without waiting out the staleness window."""
    _write_peer(tmp_path / "pod", 1, status="failed")
    sup = _elastic_supervisor(tmp_path, [1, 0], [None, None, None, 2])
    res = sup.run()
    assert res.outcomes() == ["host-lost", "clean"]
    assert "crash-loop" in sup._lost_why[1]
    assert "host death" not in sup._lost_why[1]


def test_elastic_min_world_floor_aborts(tmp_path):
    """Below --min_world the supervisor aborts with a diagnosis instead of
    training degenerately narrow, and publishes 'failed' so peers (if any
    were left) classify it."""
    from ml_recipe_tpu.resilience.coordination import read_coordination_json

    _write_peer(tmp_path / "pod", 1, age=120.0)
    sup = _elastic_supervisor(
        tmp_path, [1], [None, None], host_timeout=5.0, min_world=2,
    )
    res = sup.run()
    assert res.status == "world-floor"
    assert res.exit_code == 2
    assert res.outcomes() == ["host-lost"]
    assert "--min_world floor of 2" in res.diagnosis
    assert "host 1" in res.diagnosis
    own = read_coordination_json(tmp_path / "pod" / "host-000.json")
    assert own["status"] == "failed"


def test_elastic_losing_host0_aborts_when_peers_remain(tmp_path):
    """Host 0 carries the rendezvous coordinator address: losing it with
    >1 survivors cannot re-form a pod — abort with the reason, don't hang
    in a rendezvous that can never complete."""
    _write_peer(tmp_path / "pod", 0, age=120.0)
    _write_peer(tmp_path / "pod", 2)  # healthy third host
    sup = _elastic_supervisor(
        tmp_path, [1], [None, None], host=1, n_hosts=3, host_timeout=5.0,
    )
    res = sup.run()
    assert res.status == "coordinator-lost"
    assert res.outcomes() == ["host-lost"]
    assert "host 0" in res.diagnosis and "rendezvous" in res.diagnosis


def test_elastic_sole_survivor_continues_without_host0(tmp_path):
    """A SINGLE survivor needs no rendezvous: losing host 0 when you are
    the only host left means continuing solo, not aborting."""
    _write_peer(tmp_path / "pod", 0, age=120.0)
    sup = _elastic_supervisor(
        tmp_path, [1, 0], [None, None, None, 5], host=1, n_hosts=2,
        host_timeout=5.0,
    )
    res = sup.run()
    assert res.status == "clean"
    assert res.outcomes() == ["host-lost", "clean"]
    assert sup.world["size"] == 1 and sup.world["rank"] == 0


def test_elastic_done_peer_is_not_polled_or_lost(tmp_path):
    """A peer that finished cleanly leaves the poll set: its (aging)
    document must never be misread as a dead host."""
    _write_peer(tmp_path / "pod", 1, status="done")
    sup = _elastic_supervisor(tmp_path, [0], [None, 3], host_timeout=5.0)
    res = sup.run()
    assert res.status == "clean"
    assert res.outcomes() == ["clean"]
    assert sup._done_hosts == {1}
    assert sup.live_hosts() == [0, 1]


# ---------------------------------------------------------------------------
# Shrunk-mesh ParallelPlan re-derivation (elastic resume)
# ---------------------------------------------------------------------------


def _live_devices(n):
    import jax

    return jax.devices()[:n]


def test_elastic_plan_shrinks_data_axis():
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    plan = ParallelPlan.elastic_from_spec("data:8", devices=_live_devices(4))
    assert plan.describe() == {"data": 4}
    assert plan.shrunk
    assert plan.requested_axes == {"data": 8}


def test_elastic_plan_that_fits_is_not_shrunk():
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    plan = ParallelPlan.elastic_from_spec("data:4", devices=_live_devices(4))
    assert plan.describe() == {"data": 4}
    assert not plan.shrunk
    # fixed-world plans never report shrunk (requested_axes unset)
    assert not ParallelPlan.from_spec("data:4", devices=_live_devices(4)).shrunk


def test_elastic_plan_preserves_structural_axes():
    """Only the data axis narrows: a pipe-bearing request over half the
    devices keeps its pipeline depth and halves data parallelism."""
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    plan = ParallelPlan.elastic_from_spec(
        "data:4,pipe:2", devices=_live_devices(4)
    )
    assert plan.describe() == {"pipe": 2, "data": 2}
    assert plan.shrunk
    assert plan.requested_axes == {"pipe": 2, "data": 4}


def test_elastic_plan_refuses_structural_shrink():
    """pipe/seq/model change what each device OWNS — an elastic restart
    must refuse loudly, never silently train a different model shape."""
    from ml_recipe_tpu.parallel.mesh import ElasticMeshError
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    with pytest.raises(ElasticMeshError, match="Only the data axis"):
        ParallelPlan.elastic_from_spec(
            "data:2,pipe:8", devices=_live_devices(4)
        )


def test_elastic_plan_enforces_min_data_floor():
    from ml_recipe_tpu.parallel.mesh import ElasticMeshError
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    with pytest.raises(ElasticMeshError, match="min_world"):
        ParallelPlan.elastic_from_spec(
            "data:8", devices=_live_devices(2), min_data=4
        )


def test_elastic_plan_zero1_repads_on_shrunk_mesh():
    """The ZeRO-1 planner re-derives padding from the LIVE data-axis size:
    a leaf padded to 24 under data:8 re-pads to 20 under the shrunk
    data:4 — stale padding would corrupt the crop/zero-fill restore."""
    from ml_recipe_tpu.parallel.plan import ParallelPlan

    tree = {"mu": np.zeros(18, np.float32)}
    full = ParallelPlan.from_spec("data:8", devices=_live_devices(8))
    shrunk = ParallelPlan.elastic_from_spec("data:8", devices=_live_devices(4))
    zfull = full.zero1(tree, min_size=0)
    zshrunk = shrunk.zero1(tree, min_size=0)
    assert zfull["mu"].padded == 24    # ceil(18/8) * 8
    assert zshrunk["mu"].padded == 20  # ceil(18/4) * 4: re-derived
    assert zshrunk["mu"].axis == 0


# ---------------------------------------------------------------------------
# End-to-end elastic chaos: host death mid-collective, shrunk-mesh resume
# ---------------------------------------------------------------------------

# Two "hosts" (2 devices each, 4 global). Per step each child: fires the
# fault site, does work, beats its child heartbeat, then meets the others
# at a FILE barrier with a deliberately long timeout — the stand-in for a
# collective that never returns once a participant dies. Host 0 (rank 0)
# appends goodput windows and saves a sharded checkpoint after each
# barrier. The mesh comes from ParallelPlan.elastic_from_spec over the
# devices of the CURRENT world (MLRT_ELASTIC_WORLD), so a shrunk relaunch
# re-derives data:4 -> data:2.
_ELASTIC_CHILD = textwrap.dedent(
    """
    import json, os, pathlib, sys, time
    import numpy as np

    size, rank = (int(x) for x in os.environ["MLRT_ELASTIC_WORLD"].split(":"))
    host = int(os.environ["MLRT_HOST"])

    from ml_recipe_tpu.parallel.plan import ParallelPlan
    from ml_recipe_tpu.resilience import faults
    from ml_recipe_tpu.resilience.coordination import write_child_heartbeat
    from ml_recipe_tpu.metrics.flightrec import FlightRecorder
    from ml_recipe_tpu.metrics.goodput import append_event
    from ml_recipe_tpu.train.checkpoint import (
        load_state_dict, peek_global_step, save_state_dict_sharded,
    )

    exp = pathlib.Path(sys.argv[1])
    n_steps = int(sys.argv[2])
    barrier_timeout = float(sys.argv[3])
    ckpt = str(exp / "last.ch")
    ledger = str(exp / "goodput.jsonl")
    coord_dir = exp / "pod"
    barrier_dir = exp / "barrier"
    barrier_dir.mkdir(exist_ok=True)

    plan = ParallelPlan.elastic_from_spec("data:4")
    (exp / f"plan-w{size}-h{host}.json").write_text(json.dumps({
        "axes": plan.describe(), "shrunk": plan.shrunk,
        "requested": plan.requested_axes,
    }))
    if plan.shrunk and rank == 0:
        rec = FlightRecorder.open_in(str(exp), process_index=10 + host)
        rec.record("mesh_shrunk", old=plan.requested_axes,
                   new=plan.describe())
        rec.dump("elastic")

    params = {"w": np.zeros(4, dtype=np.float32)}
    start = 0
    if peek_global_step(ckpt) is not None:
        params, _, _, got = load_state_dict(ckpt, params=params)
        start = got or 0
    if rank == 0:
        append_event(ledger, "run_start", step=start + 1)

    def barrier(step):
        # the "collective": every rank of the CURRENT world must arrive.
        # A dead participant wedges everyone else until barrier_timeout
        # (exit 99) — unless the supervisor kills us first, which is the
        # entire point of cross-host supervision.
        (barrier_dir / f"s{step}-w{size}-h{rank}.ok").write_text("ok")
        deadline = time.monotonic() + barrier_timeout
        for r in range(size):
            want = barrier_dir / f"s{step}-w{size}-h{r}.ok"
            while not want.exists():
                if time.monotonic() > deadline:
                    sys.stderr.write(f"BARRIER TIMEOUT at step {step}\\n")
                    os._exit(99)
                time.sleep(0.01)

    for step in range(start + 1, n_steps + 1):
        faults.fire("trainer.step")
        t0 = time.time()
        time.sleep(0.05)
        params = {"w": params["w"] + 1.0}
        write_child_heartbeat(coord_dir, host, step=step)
        if rank == 0:
            append_event(ledger, "steps", first_step=step, last_step=step,
                         steps=1, productive_s=time.time() - t0)
        barrier(step)
        if rank == 0:
            save_state_dict_sharded(ckpt, params=params, global_step=step)
    print(f"DONE host={host} step={n_steps} w0={float(params['w'][0])}")
    """
)

_BARRIER_TIMEOUT = 120.0  # the "collective timeout" survivors must beat


def _elastic_child_env(size, rank, host):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MLRT_FAULTS"] = "trainer.step:kill@4%host1"
    env["MLRT_HOST"] = str(host)
    env["MLRT_ELASTIC_WORLD"] = f"{size}:{rank}"
    # 2 devices per live host: the child's jax.devices() IS the live world
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={2 * size}"
    )
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_chaos_host_death_shrinks_mesh_and_resumes(tmp_path):
    """ISSUE-16 acceptance drill: trainer.step:kill@4%host1 kills host 1's
    child at step 4; host 1's "machine" dies with it (its supervisor goes
    silent). Host 0's child wedges at the step-4 collective; its elastic
    supervisor must classify the silence as host death, kill the wedged
    child WITHOUT waiting out the collective timeout, relaunch on the
    shrunk world (data:4 -> data:2), resume from the step-3 checkpoint and
    run to completion — with the goodput ledger partitioning the run
    exactly and naming the lost host."""
    import json as json_mod

    from ml_recipe_tpu.metrics.flightrec import FLIGHTREC_PREFIX
    from ml_recipe_tpu.metrics.goodput import read_ledger, summarize_events
    from ml_recipe_tpu.resilience.coordination import PodCoordinator
    from ml_recipe_tpu.resilience.supervisor import ElasticSupervisor
    from ml_recipe_tpu.train.checkpoint import peek_global_step

    exp = tmp_path / "exp"
    exp.mkdir()
    script = exp / "child.py"
    script.write_text(_ELASTIC_CHILD)
    ckpt = str(exp / "last.ch")
    n_steps = 5

    def spawn(size, rank, host, tag):
        fh = open(exp / f"{tag}.log", "ab")
        return subprocess.Popen(
            [sys.executable, str(script), str(exp), str(n_steps),
             str(_BARRIER_TIMEOUT)],
            env=_elastic_child_env(size, rank, host),
            cwd=REPO_ROOT, stdout=fh, stderr=fh,
        )

    # -- host 1: the doomed host. Its "supervisor" publishes heartbeats
    # while its child lives; when the fault kills the child the whole host
    # is gone — silence, no terminal publish, no restart.
    doomed = {}

    def run_doomed_host():
        coord = PodCoordinator(exp / "pod", host=1, n_hosts=2)
        coord.publish("running", generation=0, attempt=0)
        child = spawn(2, 1, 1, "host1")
        while child.poll() is None:
            coord.publish("running", generation=0, attempt=0,
                          step=coord.child_step(1))
            time.sleep(0.1)
        doomed["rc"] = child.returncode

    host1 = threading.Thread(target=run_doomed_host)
    host1.start()

    # -- host 0: the real ElasticSupervisor (as _supervise_elastic wires
    # it, with drill-speed timeouts)
    sup_holder = []

    def launch(attempt_i):
        world = sup_holder[0].world
        return spawn(world["size"], world["rank"], 0, f"host0-a{attempt_i}")

    sup = ElasticSupervisor(
        launch,
        coordinator=PodCoordinator(exp / "pod", host=0, n_hosts=2),
        host_timeout=2.0,
        poll_interval=0.25,
        min_world=1,
        kill_grace=5.0,
        progress=lambda: peek_global_step(ckpt, retries=2),
        policy=_FAST_POLICY,
        attempt_timeout=240,
        state_path=str(exp / "supervisor_state.json"),
        ledger_path=str(exp / "goodput.jsonl"),
        flight_dir=str(exp),
    )
    sup_holder.append(sup)
    t0 = time.monotonic()
    result = sup.run()
    elapsed = time.monotonic() - t0
    host1.join(timeout=30)
    assert not host1.is_alive()

    # host 1 died to the injected kill, scoped to it alone
    assert doomed["rc"] == KILL_EXIT_CODE

    # the survivor restarted WITHOUT waiting out the collective timeout:
    # its wedged child was killed by the supervisor (signal), not by the
    # barrier deadline (exit 99)
    assert result.status == "clean", result.diagnosis
    assert result.outcomes() == ["host-lost", "clean"]
    assert result.attempts[0].returncode != 99
    assert result.attempts[0].returncode < 0  # killed by signal
    assert elapsed < _BARRIER_TIMEOUT / 2
    assert "host death" in sup._lost_why[1]

    # shrunk-mesh resume: gen-1 ran the requested data:4; the relaunch
    # re-derived data:2 over the surviving world and resumed from step 3
    full = json_mod.loads((exp / "plan-w2-h0.json").read_text())
    assert full == {"axes": {"data": 4}, "shrunk": False,
                    "requested": {"data": 4}}
    shrunk = json_mod.loads((exp / "plan-w1-h0.json").read_text())
    assert shrunk == {"axes": {"data": 2}, "shrunk": True,
                      "requested": {"data": 4}}
    assert result.attempts[0].step_after == 3   # step-4 save never landed
    assert result.attempts[1].step_before == 3
    assert peek_global_step(ckpt) == n_steps
    assert f"DONE host=0 step={n_steps} w0={float(n_steps)}" in (
        (exp / "host0-a1.log").read_text(errors="replace")
    )

    # goodput ledger: exact partition, restart downtime and the recomputed
    # step 4 both visible, the lost host counted
    events = read_ledger(exp / "goodput.jsonl")
    s = summarize_events(events)
    assert s["attempts"] == 2
    assert s["hosts_lost"] == 1
    assert s["badput_s"]["restart_downtime"] > 0
    assert s["badput_s"]["recompute"] > 0
    assert s["recomputed_steps"] == 1  # step 4 ran, was lost, ran again
    accounted = s["productive_s"] + sum(s["badput_s"].values())
    assert accounted == pytest.approx(s["total_wall_s"], rel=1e-9)

    # flight recorder: the elastic transitions are on disk — host_lost
    # from the supervisor, mesh_shrunk from the shrunk child
    kinds = set()
    for path in exp.glob(f"{FLIGHTREC_PREFIX}*.json"):
        doc = json_mod.loads(path.read_text())
        kinds.update(e["kind"] for e in doc.get("events", []))
    assert "host_lost" in kinds
    assert "mesh_shrunk" in kinds

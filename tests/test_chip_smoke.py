"""chip_smoke.py off the chip: it must refuse to run here, and its phases
must work — at bert-tiny size, on the CPU, called with arguments (the
program has no size option) — so that a chip call is not spent finding a
wrong path or argument. Also the contracts the smoke leans on: where the
compile cache lands, that the --supervise parent leaves the chip to its
child, and that fleet engines get one chip each.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY_TRAIN = dict(model="bert-tiny", max_seq_len=16, max_question_len=4,
                  train_batch_size=2000, test_batch_size=512, batch_split=2,
                  n_jobs=2)


def _python(code: str, *, env=None, cwd=REPO, timeout=120):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=str(cwd), text=True,
        capture_output=True, timeout=timeout,
        env={**os.environ, **(env or {})},
    )


def test_exits_nonzero_without_a_chip():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        text=True, capture_output=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_parity_phase_under_the_interpreter():
    # the phase reports the process-wide autotuner's decisions at its sequence
    # length: start from a fresh one, whatever ran in this worker before
    from ml_recipe_tpu.ops import autotune

    autotune.reset()
    report = chip_smoke.parity_phase(
        0, shape=dict(B=2, L=128, H=2, D=64), on_chip=False)
    assert max(report["max_err_over_max_ref"].values()) <= report["tolerance"]
    assert report["block_until_ready"]["blocks"]
    assert set(d["regime"] for d in report["attention"]["decisions"].values()
               ) == {"fused_fwd_lse", "fused_bwd"}
    assert report["compile"]["programs"] > 0


def test_train_phase_through_the_cli(tmp_path):
    report = chip_smoke.train_phase(
        0, tmp_path / "train", overrides=TINY_TRAIN, on_chip=False)
    assert report["optimizer_steps"] == 5  # 10,000 dummy items / 2,000
    assert len(set(report["step_losses_first3"])) == 3
    assert report["last_ch_bytes"] > 0
    assert len(report["step_wall_s_first3"]) == 3
    assert report["tokenizer_backend"] == "native"
    assert report["compile_cache"]["dir"] == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or str(REPO / ".jax_cache"))
    # the device-only conditions have teeth: on the CPU attention runs XLA,
    # and the checks that hold a chip run to the Pallas path refuse that
    with pytest.raises(chip_smoke.SmokeFailure, match="Pallas kernels"):
        chip_smoke._attention_report(True, 16, {"fused_fwd_lse"})


def test_serve_phase_through_the_cli(tmp_path):
    work = tmp_path / "serve"
    report = chip_smoke.run_phase(
        chip_smoke.serve_child, seed=0, work=work, on_chip=False,
        overrides=dict(model="bert-tiny", buckets="8x64",
                       max_question_len=16, doc_stride=24),
        drive=lambda proc: chip_smoke.serve_client(
            proc, work, 0, doc_tokens=200),
        timeout_s=300,
    )
    assert [r["status"] for r in report["requests"]] == [200, 200, 200]
    assert all(r["n_chunks"] > 1 for r in report["requests"])
    assert report["buckets"] == ["8x64"]


def test_four_chip_phase_on_four_virtual_devices(tmp_path):
    """Its own process: four virtual CPU devices, as ``--chips 4`` sees four
    chips (this session has eight)."""
    code = (
        "import json, sys, pathlib, chip_smoke\n"
        "r = chip_smoke.multichip_phase(0, pathlib.Path(sys.argv[1]), "
        "on_chip=False, overrides=dict(model='bert-tiny', max_seq_len=16, "
        "max_question_len=4, train_batch_size=64, batch_split=2))\n"
        "print(json.dumps(r, default=str))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "mc")], cwd=str(REPO),
        text=True, capture_output=True, timeout=600,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["device"]["count"] == 4
    assert report["all_reduce_ops"] > 0
    wide, one = report["losses"]["data:4"], report["losses"]["data:1"]
    assert len(wide) == len(one) == 3
    assert wide == pytest.approx(one, rel=1e-4)  # f32 on the CPU
    # attention dropout live (batch_split 2 on data:4: the data island):
    # the masks are one device's, XLA attention's here as the kernels' on
    # the chip
    assert report["attention_dropout_step"]["same_within_rtol"]
    drop = report["attention_dropout_step"]["losses"]
    assert drop["data:4"] == pytest.approx(drop["data:1"], rel=1e-4)

PLACE = (
    "import os, jax\n"
    "from ml_recipe_tpu.utils.platform import configure_compile_cache\n"
    "print(configure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
)


def test_compile_cache_follows_the_environment_variable(tmp_path, monkeypatch):
    """Set from outside: jax reads the variable itself, and the code names
    no other directory."""
    import jax

    from ml_recipe_tpu.utils.platform import configure_compile_cache

    placed = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: placed.append(k))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert placed and "jax_compilation_cache_dir" not in placed

    out = _python(PLACE, env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path), str(tmp_path), "0.0"]


def test_compile_cache_defaults_to_one_path_in_the_checkout(tmp_path):
    """Unset: two different processes, started in different directories,
    land on the same fixed path inside the checkout."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO)
    seen = []
    for cwd in (REPO, tmp_path):
        out = subprocess.run(
            [sys.executable, "-c", PLACE], cwd=str(cwd), text=True,
            capture_output=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        seen.append(out.stdout.split())
    assert seen[0] == seen[1] == [str(REPO / ".jax_cache")] * 2 + ["0.0"]


def test_supervise_parent_initialises_no_backend(tmp_path):
    """``--supervise``: the parent launches the training child, and a chip
    belongs to one process — so when the child starts, the parent must not
    have initialised a JAX backend."""
    vocab = tmp_path / "vocab.txt"
    chip_smoke.write_vocab(vocab, 0, n_words=50)
    code = f"""
import subprocess, sys
from jax._src import xla_bridge
from ml_recipe_tpu.resilience import supervisor
seen = []
class FakeChild:
    def __init__(self, argv, **kw):
        seen.append(xla_bridge.backends_are_initialized())
        self.returncode = 0
        self.pid = 0
    def wait(self, timeout=None): return 0
    def poll(self): return 0
supervisor.subprocess.Popen = FakeChild
sys.argv = ["train", "--supervise", "--model", "bert-tiny", "--dummy_dataset",
            "--vocab_file", {str(vocab)!r}, "--dump_dir", {str(tmp_path)!r},
            "--experiment_name", "sup", "--max_restarts", "0"]
from ml_recipe_tpu.cli import train
try:
    train.cli()
except SystemExit as e:
    print("exit", e.code)
print("launched", seen, "after", xla_bridge.backends_are_initialized())
"""
    out = _python(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "launched [False] after False" in out.stdout, out.stdout


def test_fleet_engines_get_one_chip_each(tmp_path, monkeypatch):
    from ml_recipe_tpu.fleet import manager

    launched = []

    class FakeChild:
        pid = 0

        def __init__(self, argv, env=None, **kw):
            launched.append(env)

    # off the TPU nothing is counted, so nothing is confined
    assert manager.host_tpu_chips({"JAX_PLATFORMS": "cpu"}) == 0
    monkeypatch.setattr(manager.subprocess, "Popen", FakeChild)
    monkeypatch.setattr(manager, "host_tpu_chips", lambda env: 4)
    fleet = manager.FleetManager(["--model", "bert-tiny"], n_engines=4,
                                 run_dir=tmp_path, env={})
    for handle in fleet.engines:
        fleet._launch(handle)
    assert [e["TPU_VISIBLE_CHIPS"] for e in launched] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in launched)
    # more engines than chips would leave engines waiting for a chip that
    # never frees: an error at start
    with pytest.raises(manager.FleetError, match="4 TPU chip"):
        manager.FleetManager(["--model", "bert-tiny"], n_engines=5,
                             run_dir=tmp_path, env={})
    launched.clear()
    monkeypatch.setattr(manager, "host_tpu_chips", lambda env: 0)
    cpu = manager.FleetManager(["--model", "bert-tiny"], n_engines=2,
                               run_dir=tmp_path, env={"JAX_PLATFORMS": "cpu"})
    cpu._launch(cpu.engines[0])
    assert "TPU_VISIBLE_CHIPS" not in launched[0]

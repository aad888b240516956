"""The benchmark's runners off the chip: without a TPU the command refuses;
with ``--rehearse`` (tests only) each runner goes through its whole course at
bert-tiny size on the CPU and prints a last line with exactly the contract's
keys, ``device.platform: cpu`` and no number under a metric's name. Also the
plain reference against ``QAModel`` (XLA attention, float32)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def manifest_with_waiting_cells(tmp_path_factory):
    """``BENCHMARK.json`` plus the two cells whose entries wait in their
    traffic files (PERF.md says why they are not cells yet): their runners
    and readers are rehearsed all the same."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for traffic in ("nqmix", "steady"):
        extra = json.loads((REPO / "perfbench/traffic" / f"{traffic}.json")
                           .read_text())["manifest"]
        manifest["workloads"].append(extra["workload"])
        manifest["end_to_end"] += extra["end_to_end"]
        manifest["per_layer"] += extra["per_layer"]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return path


def _run(*args, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "-c", _OUT_OF_THE_WAY,
         str(REPO / "perfbench" / "run.py"), *args],
        cwd=str(REPO), env=env, text=True, capture_output=True,
        timeout=timeout)


# The command runs on one core and at the lowest priority: the suite has tests
# that time things, and a rehearsal's compile and loader threads must not take
# their cores. (Set in the child itself: a preexec_fn forks a threaded parent.)
_OUT_OF_THE_WAY = (
    "import os, runpy, sys; os.nice(19); "
    "os.sched_setaffinity(0, {max(os.sched_getaffinity(0))}); "
    "sys.argv = sys.argv[1:]; "
    "runpy.run_path(sys.argv[0], run_name='__main__')")


def _lines(out):
    return [json.loads(x) for x in out.stdout.splitlines()
            if x.startswith("{")]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    out = _run("--workload", "base-train-full512", "--seed", "0",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no TPU here" in out.stderr
    assert not any("metrics" in line for line in _lines(out))


def test_an_unknown_workload_is_refused():
    out = _run("--workload", "no-such-cell", "--seed", "0")
    assert out.returncode != 0 and "no workload" in out.stderr


# slow: four subprocesses of jax compiles, a minute of one core. Inside the
# tier-1 run (six workers on eight cores) they were enough to tip the timing
# test tests/test_parallel_plan.py::test_pipe_schedule_overlap_is_real, which
# fails one run in five alone, into failing four runs of five (PR 22).
@pytest.mark.slow
@pytest.mark.parametrize("workload,trace,devices", [
    ("base-train-full512", 0, 1),
    ("base-train-nqmix", 1, 1),
    ("large-train-dp4", 0, 4),
    ("base-serve-steady", 1, 1),
])
def test_rehearsal_prints_the_contracts_last_line(
        workload, trace, devices, manifest_with_waiting_cells):
    out = _run("--workload", workload, "--seed", "11", "--seconds", "3",
               "--trace", str(trace), "--rehearse", "--manifest",
               str(manifest_with_waiting_cells), devices=devices)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = _lines(out)
    last = lines[-1]
    assert set(last) == LAST_LINE_KEYS
    assert last["correct"] is True, lines[-4:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {}, "no CPU number under a metric's name"
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == devices
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    said = {k: v for line in lines[:-1] for k, v in line.items()}
    assert said["correct"]["window_compiles"] == 0
    assert said["reference_check"]["ok"] is True
    if workload == "base-train-nqmix":
        shapes = said["setup"]["warmup_shapes"]
        assert len(shapes) == 4, "every bucket's program ran in the warm-up"
        assert said["stretches"]["traced"]["steps"] >= 1
        assert said["stretches"]["telemetry"]["steps"] >= 2
    if workload == "large-train-dp4":
        assert said["run"]["workload"] == workload
        assert said["mesh"] == {"data": 4}
        assert "mesh_loss" in said["reference_check"]


def test_plain_reference_agrees_with_qamodel_on_bert_tiny():
    import dataclasses
    import types

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import QAModel
    from ml_recipe_tpu.models.config import MODEL_PRESETS
    from perfbench.harness import checks, reference

    cfg = dataclasses.replace(MODEL_PRESETS["bert-tiny"],
                              hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0)
    model = QAModel(cfg, dtype=jnp.float32, attention_impl="xla")
    inputs, labels = checks.seeded_rows(0, cfg.vocab_size, 48, [48, 30, 17, 9])
    params = model.init(jax.random.key(0), inputs["input_ids"])["params"]
    # flax starts biases at zero: move them, or a dropped bias would not show
    params = jax.tree_util.tree_map(
        lambda x: x + 0.02 * jax.random.normal(jax.random.key(1), x.shape),
        params)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, **inputs, deterministic=True)
    ref_cfg = {"num_hidden_layers": 2, "num_attention_heads": 2,
               "hidden_size": 128, "intermediate_size": 512}
    want = reference.forward(jax.device_get(params), ref_cfg, **inputs)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert max(errors.values()) < 1e-5, errors       # float32 against float32
    loss_fn = build_loss(types.SimpleNamespace(loss="smooth", smooth_alpha=0.01))
    system_loss = float(loss_fn(got, {k: jnp.asarray(v)
                                      for k, v in labels.items()})[0])
    assert system_loss == pytest.approx(
        float(reference.loss(want, labels, smooth_alpha=0.01)), rel=1e-6)
    # a dropped term must land outside the benchmark's tolerance
    broken = jax.tree_util.tree_map(lambda x: x, jax.device_get(params))
    broken["transformer"]["layer_0"]["attention"]["query"]["bias"] = np.zeros(
        128, np.float32) + 1.0
    off = reference.forward(broken, ref_cfg, **inputs)
    tolerances = checks.logit_tolerances(jax.device_get(params), 2)
    assert tolerances["start_reg"] == 2 * 2.0 ** -7
    assert not checks.within(
        checks.absolute_errors(off, want, inputs["attention_mask"]),
        tolerances)

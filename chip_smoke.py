"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of bert-base-uncased with random weights made from ``--seed``:

- ``parity``  the compiled Pallas attention of ``ops/attention.py`` against
              float32 XLA attention at the train micro-batch shape;
- ``train``   ``ml_recipe_tpu.cli.train`` on ``config/test_bert.cfg`` shapes
              (seq 512, global batch 256, dummy dataset): one epoch of
              optimizer steps, the test pass, ``last.ch``;
- ``serve``   ``ml_recipe_tpu.cli.serve`` with one ``8x512`` bucket answering
              three ``POST /v1/qa`` requests, then a SIGTERM drain.

``--chips 4`` runs instead — and only — the data-parallel phase: the same
train configuration for three optimizer steps under ``--mesh data:4`` and
under ``data:1`` in one process, loss trajectories compared.

One process uses the chip at a time: this parent never imports jax, and each
phase is a spawned child that exits before the next starts. Every phase
prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
There is no CPU fallback: without a TPU the first child fails in seconds and
the script exits non-zero without printing a result.

Step times printed here are smoke observations of a cold or warm start,
never a rate.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import math
import multiprocessing
import os
import signal
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent

# config/test_bert.cfg is the train configuration; these are the smoke's
# departures from it. ``None`` drops the key. debug goes: debug mode caps the
# run at two steps and skips every checkpoint write. length_buckets goes off:
# dummy items are all full length, and 'auto' would compile three more
# bucket programs nothing feeds.
TRAIN_OVERRIDES = {
    "debug": None,
    "n_epochs": 1,
    "length_buckets": "off",
    "log_every": 1,
    "flight_recorder": True,
    "experiment_name": "smoke",
}
# bf16 (8 mantissa bits, eps 2^-7 = 7.8e-3). Parity: kernel and reference
# see the same bf16 operands and accumulate in f32; the kernel rounds its
# outputs (and the probabilities it feeds the MXU) to bf16, so the largest
# error allowed is two bf16 ulps of the largest reference element.
# Multichip: data:4 and data:1 differ only in the order of f32 reductions
# over bf16 products.
PARITY_TOL = 2 * 2.0 ** -7
MULTICHIP_RTOL = 1e-2
PARITY_SHAPE = dict(B=32, L=512, H=12, D=64)


class SmokeFailure(RuntimeError):
    """A phase ran but what came out is not right."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- set-up shared by the phases (runs inside the phase's process) -------------

@contextlib.contextmanager
def _phase(name: str, on_chip: bool):
    """Bookkeeping every phase shares: the device (no TPU, no run), versions,
    the compile cache placed exactly as the entry points place it and its
    entry counts, compile seconds as JAX itself accounts them (the time spent
    obtaining executables: a real compile on a cache miss, a read on a hit),
    wall time and peak device memory."""
    import jax
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version

    from ml_recipe_tpu.utils.platform import configure_compile_cache

    t0 = time.perf_counter()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if on_chip and device["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0] is {devices[0]}")
    cache_dir = configure_compile_cache()
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    compiles = {"seconds": 0.0, "programs": 0, "cache_hits": 0,
                "cache_misses": 0, "missed": []}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["seconds"] += seconds
            compiles["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compiles["cache_misses"] += 1

    class MissNames(logging.Filter):
        """jax names the module of each persistent-cache miss in a DEBUG
        record; note the name, and keep DEBUG records off the handlers."""

        def filter(self, record):
            if record.levelno > logging.DEBUG:
                return True
            if "CACHE MISS for" in str(record.msg) and record.args:
                compiles["missed"].append(str(record.args[0]))
            return False

    compiler_log = logging.getLogger("jax._src.compiler")
    miss_names, saved_level = MissNames(), compiler_log.level
    compiler_log.addFilter(miss_names)
    compiler_log.setLevel(logging.DEBUG)

    report = {
        "phase": name, "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache": {"dir": cache_dir,
                          "entries_before": _cache_entries(cache_dir)},
        "compile": compiles,
    }
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield report
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
        compiler_log.removeFilter(miss_names)
        compiler_log.setLevel(saved_level)
    compiles["seconds"] = round(compiles["seconds"], 3)
    if len(compiles["missed"]) > 12:  # a cold start misses everything
        compiles["missed"] = f"{len(compiles['missed'])} programs"
    report["compile_cache"]["entries_after"] = _cache_entries(cache_dir)
    stats = devices[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["wall_s"] = round(time.perf_counter() - t0, 3)


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


def write_vocab(path: Path, seed: int, n_words: int = 2000) -> None:
    """A WordPiece vocabulary from ``seed`` (the machine has no network and
    /data/ is not in git). The preset fixes the embedding table at its
    published 30,522 rows whatever this file holds."""
    import numpy as np

    rng = np.random.default_rng(seed)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    words = sorted({
        "".join(rng.choice(letters, size=int(rng.integers(3, 9))))
        for _ in range(n_words)
    })
    specials = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
                "<p>", "</p>", ".", "?", ","]
    path.write_text("\n".join(specials + words) + "\n")


def write_train_config(work: Path, seed: int, overrides: dict) -> Path:
    """config/test_bert.cfg with the smoke's departures applied."""
    work.mkdir(parents=True, exist_ok=True)
    write_vocab(work / "vocab.txt", seed)
    values = {}
    for line in (REPO / "config" / "test_bert.cfg").read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#") and "=" in line:
            key, value = line.split("=", 1)
            values[key.strip()] = value.strip()
    values.update(TRAIN_OVERRIDES)
    values.update({
        "seed": seed,
        "vocab_file": work / "vocab.txt",
        "dump_dir": work / "results",
        "data_path": work / "data.jsonl",
        "processed_data_path": work / "processed",
    })
    values.update(overrides or {})
    path = work / "smoke.cfg"
    path.write_text("".join(
        f"{k}={v}\n" for k, v in values.items() if v is not None))
    return path


@contextlib.contextmanager
def _recording(module, name: str, **extra_kwargs):
    """Swap ``module.name`` for a subclass that remembers its instances (and
    passes ``extra_kwargs`` to the constructor): the CLI builds its Trainer /
    QAEngine out of reach, and the smoke has to read their reports. The entry
    point's own code path is untouched."""
    cls = getattr(module, name)
    made = []

    class Recorded(cls):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs, **extra_kwargs)
            made.append(self)

    Recorded.__name__, Recorded.__qualname__ = cls.__name__, cls.__qualname__
    setattr(module, name, Recorded)
    try:
        yield made
    finally:
        setattr(module, name, cls)


@contextlib.contextmanager
def _argv(*args: str):
    saved = sys.argv
    sys.argv = list(args)
    try:
        yield
    finally:
        sys.argv = saved


def _attention_report(on_chip: bool, seq: int, want_regimes) -> dict:
    """What the process-wide autotuner decided at sequence length ``seq``
    (the program's; the model's 8-token init example also passes through the
    dispatcher), and the check that attention ran the Pallas kernels at a
    probe-validated geometry."""
    from ml_recipe_tpu.ops import autotune

    summary = autotune.get().session_summary()
    decisions = {key: d for key, d in summary["decisions"].items()
                 if f"|L{seq}|" in key}
    if on_chip:
        ran = {d["regime"] for d in decisions.values()}
        _require(
            set(want_regimes) <= ran,
            f"attention did not run the Pallas kernels at L={seq}: wanted "
            f"regimes {sorted(want_regimes)}, autotuner decided {sorted(ran)}")
        for key, d in decisions.items():
            _require(
                d["source"] == "probe" and d["geometry"] is not None,
                f"geometry of {key} was not validated by a compile probe on "
                f"this device: {d}")
    return {"probes": summary["probes"], "cache": summary["cache"],
            "decisions": decisions}


# -- parity --------------------------------------------------------------------

def parity_phase(seed: int, *, shape=None, on_chip: bool = True) -> dict:
    """Compiled Pallas attention vs float32 XLA attention: forward output and
    dq/dk/dv, dropout 0, bf16 operands. Also checks once that
    ``block_until_ready`` waits for the device on this backend."""
    with _phase("parity", on_chip) as report:
        _parity(report, seed, dict(PARITY_SHAPE, **(shape or {})), on_chip)
    return report


def _parity(report: dict, seed: int, shape: dict, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops.attention import (
        _xla_attention,
        dot_product_attention,
    )
    from ml_recipe_tpu.ops.flash_attention import flash_attention

    B, L, H, D = shape["B"], shape["L"], shape["H"], shape["D"]
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    q, k, v, g = (
        jax.random.normal(key, (B, L, H, D), jnp.float32).astype(jnp.bfloat16)
        for key in (kq, kk, kv, kg)
    )
    # ragged key-validity mask, as padded QA chunks have
    lengths = np.linspace(L // 2, L, B).astype(np.int32)
    mask = jnp.asarray(np.arange(L)[None, :] < lengths[:, None], jnp.int32)

    if on_chip:
        def kernel(q, k, v):
            return dot_product_attention(
                q, k, v, mask, dtype=jnp.bfloat16, impl="pallas")
    else:  # CPU rehearsal: the same kernels under the Pallas interpreter
        def kernel(q, k, v):
            return flash_attention(
                q, k, v, mask, dtype=jnp.bfloat16, interpret=True)

    def reference(q, k, v):
        return _xla_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), mask, dtype=jnp.float32)

    def fwd_and_grads(fn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(g.astype(out.dtype)))
        return jax.jit(run)

    got = jax.block_until_ready(fwd_and_grads(kernel)(q, k, v, g))
    want = jax.block_until_ready(fwd_and_grads(reference)(q, k, v, g))

    errors = {}
    valid = np.asarray(mask, bool)[:, :, None, None]  # pad query rows: garbage
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.where(valid, np.asarray(a, np.float32), 0.0)
        b = np.where(valid, np.asarray(b, np.float32), 0.0)
        _require(bool(np.isfinite(a).all()), f"parity {name}: not finite")
        errors[name] = float(np.abs(a - b).max() / np.abs(b).max())
    report["shape"] = shape
    report["max_err_over_max_ref"] = errors
    report["tolerance"] = PARITY_TOL
    _require(max(errors.values()) <= PARITY_TOL,
             f"parity outside tolerance {PARITY_TOL}: {errors}")

    # does block_until_ready block here? Enqueue a long chain of matmuls: if
    # it returned early, the host fetch after it would carry the wait instead.
    n = 4096 if on_chip else 256
    x = jnp.ones((n, n), jnp.bfloat16)
    chain = jax.jit(lambda x: jax.lax.fori_loop(
        0, 400, lambda _, y: (y @ x) * (1.0 / n), x)[0, 0])
    jax.block_until_ready(chain(x))  # compile
    t0 = time.perf_counter()
    y = chain(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    float(y)
    t_fetch = time.perf_counter() - t0 - t_block
    report["block_until_ready"] = {
        "dispatch_s": round(t_dispatch, 4), "blocked_s": round(t_block, 4),
        "fetch_after_s": round(t_fetch, 4), "blocks": t_fetch < t_block,
    }
    _require(t_fetch < t_block, "block_until_ready returned before the "
             f"device finished: {report['block_until_ready']}")
    report["attention"] = _attention_report(
        on_chip, L, {"fused_fwd_lse", "fused_bwd"})


# -- train ---------------------------------------------------------------------

def train_phase(seed: int, work: Path, *, overrides=None,
                on_chip: bool = True) -> dict:
    """``python -m ml_recipe_tpu.cli.train -c <derived test_bert.cfg>``,
    in-process so that the Trainer's own reports can be read afterwards."""
    from ml_recipe_tpu.cli import train as train_cli
    from ml_recipe_tpu.metrics.flightrec import newest_flight_record

    cfg_path = write_train_config(work, seed, overrides)
    running_mean = []  # the Trainer's loss tap sees the epoch's running mean

    def tap(meters, *, step):
        running_mean.append(float(meters["loss"]()))

    with _phase("train", on_chip) as report, \
            _recording(train_cli, "Trainer", on_train_metrics=tap) as made, \
            _argv("ml_recipe_tpu.cli.train", "-c", str(cfg_path)):
        train_cli.cli()
        _require(len(made) == 1, f"expected one Trainer, saw {len(made)}")
        trainer = made[0]

        losses = [  # un-average the running mean into step losses
            (i + 1) * m - i * (running_mean[i - 1] if i else 0.0)
            for i, m in enumerate(running_mean)
        ]
        report["optimizer_steps"] = trainer.global_step
        report["step_losses_first3"] = [round(x, 6) for x in losses[:3]]
        report["step_loss_last"] = round(losses[-1], 6) if losses else None
        _require(
            len(losses) >= 3 and len(losses) == trainer.global_step,
            f"expected >= 3 step losses, one per step: {len(losses)} for "
            f"{trainer.global_step} steps")
        _require(all(math.isfinite(x) for x in losses),
                 f"non-finite loss: {losses}")
        _require(len({round(x, 6) for x in losses[:3]}) == 3,
                 f"the first three losses are not distinct: {losses[:3]}")

        params = trainer.trainer_params
        exp_dir = Path(params.dump_dir) / params.experiment_name
        record = newest_flight_record(exp_dir)
        _require(record is not None, f"no flight-recorder dump in {exp_dir}")
        steps = [e for e in record[1]["events"] if e["kind"] == "step"]
        report["step_wall_s_first3"] = [e["total_s"] for e in steps[:3]]
        report["tokenizer_backend"] = trainer._collate_tokenizer().backend
        report["preflight"] = trainer.preflight_report
        report["batch_split"] = trainer.batch_split
        last = exp_dir / "last.ch"
        _require(last.exists(), f"{last} was not written")
        report["last_ch_bytes"] = last.stat().st_size
        report["attention"] = _attention_report(
            on_chip, params.max_seq_len, {"fused_fwd_lse", "fused_bwd"})
        if on_chip:
            _require(report["tokenizer_backend"] == "native",
                     "the tokenizer backend is not the native one")
            _require(
                bool((report["preflight"] or {}).get("limit_bytes")),
                "the HBM pre-flight stood down for lack of a device limit")
    return report


# -- serve ---------------------------------------------------------------------

def serve_child(seed: int, work: Path, *, overrides=None,
                on_chip: bool = True) -> dict:
    """``python -m ml_recipe_tpu.cli.serve``: runs until the parent's SIGTERM
    has drained it, then reports what the engine recorded."""
    from ml_recipe_tpu.cli import serve as serve_cli
    from ml_recipe_tpu.serve import engine as engine_mod

    work.mkdir(parents=True, exist_ok=True)
    write_vocab(work / "vocab.txt", seed)
    flags = {
        "model": "bert-base-uncased", "vocab_file": work / "vocab.txt",
        "compute_dtype": "bfloat16", "flash_attention": "auto",
        "buckets": "8x512", "max_question_len": 64, "port": 0,
        "ready_file": work / "serve.ready.json",
    }
    flags.update(overrides or {})
    argv = ["--lowercase"] + [
        part for k, v in flags.items() for part in (f"--{k}", str(v))]

    with _phase("serve", on_chip) as report, \
            _recording(engine_mod, "QAEngine") as made, \
            _argv("ml_recipe_tpu.cli.serve", *argv):
        try:
            serve_cli.cli()
            code = None
        except SystemExit as e:
            code = e.code
        _require(code == 0, f"cli.serve exited with {code!r} after the drain")
        _require(len(made) == 1, f"expected one QAEngine, saw {len(made)}")
        engine = made[0]
        warm = engine.warmup_report
        report["warmup_s"] = warm["warmup_seconds"]
        report["buckets"] = warm["buckets"]
        report["preflight"] = warm["preflight"]
        report["tokenizer_backend"] = engine.tokenizer.backend
        report["attention"] = _attention_report(
            on_chip, max(b.seq for b in engine.grid), {"fused_fwd"})
        if on_chip:
            _require(report["tokenizer_backend"] == "native",
                     "the tokenizer backend is not the native one")
            _require(
                len(warm["preflight"]) == len(warm["buckets"])
                and all(v.get("limit") for v in warm["preflight"].values()),
                "the HBM pre-flight stood down for lack of a device limit")
    return report


def serve_client(proc, work: Path, seed: int, *, doc_tokens: int = 2000,
                 ready_timeout_s: float = 900.0) -> dict:
    """The parent's side of the serve phase: wait for the ready file, ask
    three questions over one long synthetic document each, SIGTERM."""
    import random

    ready = work / "serve.ready.json"
    deadline = time.monotonic() + ready_timeout_s
    while not ready.exists():
        if not proc.is_alive():
            raise SmokeFailure("the serve child died before it was ready")
        if time.monotonic() > deadline:
            raise SmokeFailure(f"serve not ready within {ready_timeout_s}s")
        time.sleep(0.2)
    info = json.loads(ready.read_text())
    words = (work / "vocab.txt").read_text().split()[10:]
    rng = random.Random(seed)
    answers = []
    try:
        for _ in range(3):
            body = json.dumps({
                "question": " ".join(rng.choices(words, k=8)) + " ?",
                "document": " ".join(rng.choices(words, k=doc_tokens)) + " .",
            }).encode()
            request = urllib.request.Request(
                f"http://{info['host']}:{info['port']}/v1/qa", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(request, timeout=120) as response:
                status = response.status
                payload = json.loads(response.read())
            _require(status == 200, f"POST /v1/qa answered {status}")
            _require(
                isinstance(payload.get("answer"), str)
                and payload.get("label") in (
                    "yes", "no", "short", "long", "unknown")
                and math.isfinite(float(payload.get("score")))
                and payload.get("n_chunks", 0) > 1,
                f"malformed answer object: {payload}")
            answers.append({
                "status": status, "label": payload["label"],
                "n_chunks": payload["n_chunks"],
                "wall_s": round(time.perf_counter() - t0, 4),
            })
    finally:
        os.kill(info["pid"], signal.SIGTERM)
    return {"requests": answers}


# -- data:4 against data:1 -----------------------------------------------------

def multichip_phase(seed: int, work: Path, *, overrides=None,
                    on_chip: bool = True, n_devices: int = 4) -> dict:
    """Three optimizer steps of the train configuration under ``data:N`` and
    under ``data:1`` on one of the chips: same seed, same batches, dropout 0.
    Then one step of each with attention dropout 0.1 alone (the Pallas
    kernels' in-kernel masks), printed as a finding and not asserted."""
    with _phase("multichip", on_chip) as report:
        _require(
            report["device"]["count"] >= n_devices,
            f"need {n_devices} devices, have {report['device']['count']}")
        _multichip(report, seed, work, overrides, on_chip, n_devices)
    return report


def _multichip(report, seed, work, overrides, on_chip, n_devices) -> None:
    import jax
    import numpy as np

    from ml_recipe_tpu.compose import (
        init_collate_fun,
        init_datasets,
        init_loss,
        init_model,
    )
    from ml_recipe_tpu.config.parser import (
        get_model_parser,
        get_params,
        get_trainer_parser,
    )
    from ml_recipe_tpu.ops import autotune
    from ml_recipe_tpu.parallel import ParallelPlan
    from ml_recipe_tpu.train import Trainer
    from ml_recipe_tpu.utils.seed import set_seed

    def build(mesh_spec: str, attention_dropout: float):
        cfg_path = write_train_config(work, seed, {
            "hidden_dropout_prob": 0.0,
            "attention_probs_dropout_prob": attention_dropout,
            **(overrides or {}),
        })
        _, (params, model_params) = get_params(
            (get_trainer_parser, get_model_parser), ["-c", str(cfg_path)])
        autotune.configure(enabled=params.autotune)
        plan = ParallelPlan.from_spec(mesh_spec)
        rng_pool = set_seed(seed)
        model, model_state, tokenizer = init_model(
            model_params, rng_seed=seed, mesh=plan.mesh)
        train_dataset, _, weights = init_datasets(
            params, tokenizer=tokenizer, clear=False,
            rng=rng_pool.host_rng("chunk_sampling"))
        return Trainer(
            model=model, params=model_state, loss=init_loss(params, weights),
            collate_fun=init_collate_fun(
                tokenizer, max_seq_len=params.max_seq_len),
            trainer_params=params, train_dataset=train_dataset,
            mesh=plan.mesh, n_epochs=1,
            train_batch_size=params.train_batch_size,
            batch_split=params.batch_split, n_jobs=0,
            warmup_coef=params.warmup_coef,
            max_grad_norm=params.max_grad_norm, seed=seed,
            hbm_preflight=params.hbm_preflight,
        )

    def run(trainer, batches):
        """Hand-driven steps, as bench.py drives them."""
        losses, walls = [], []
        with trainer.mesh:
            trainer.preflight_train_step(*batches[0])
            if trainer._jit_train_step is None:
                trainer._jit_train_step = trainer._build_train_step()
            step = trainer._jit_train_step
            state = (trainer.params, trainer.opt_state)
            for i, (inputs, labels) in enumerate(batches):
                placed = [
                    trainer._global_batch(
                        trainer._split_micro(tree), leading_accum=True)
                    for tree in (inputs, labels)
                ]
                t0 = time.perf_counter()
                *state, values = step(*state, *placed, i)
                jax.block_until_ready(values)
                walls.append(round(time.perf_counter() - t0, 3))
                losses.append(float(values["loss"]))
            trainer.params, trainer.opt_state = state
        return losses, walls, placed

    def host_batches(trainer, n: int):
        loader = iter(trainer.train_dataloader)
        return [trainer._normalize_batch(next(loader))[:2] for _ in range(n)]

    wide = f"data:{n_devices}"
    dp = build(wide, 0.0)
    batches = host_batches(dp, 3)
    dp_losses, dp_walls, placed = run(dp, batches)

    # every device holds a batch shard and a parameter replica
    devices = jax.devices()[:n_devices]
    ids = placed[0]["input_ids"]
    shard_devices = {s.device for s in ids.addressable_shards}
    _require(shard_devices == set(devices)
             and all(s.data.shape[1] * n_devices == ids.shape[1]
                     for s in ids.addressable_shards),
             f"batch is not sharded over {wide}: {ids.sharding}")
    for leaf in jax.tree_util.tree_leaves(dp.params):
        _require(
            {s.device for s in leaf.addressable_shards} == set(devices)
            and all(s.data.shape == leaf.shape
                    for s in leaf.addressable_shards),
            f"a parameter is not replicated on every device: {leaf.sharding}")
    compiled_text = dp._jit_train_step.lower(
        dp.params, dp.opt_state, *placed, 0).compile().as_text()
    report["all_reduce_ops"] = compiled_text.count("all-reduce")
    _require(report["all_reduce_ops"] > 0,
             "the compiled data-parallel step contains no all-reduce")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    report["bytes_in_use_per_device"] = in_use
    if on_chip:
        _require(all(in_use) and max(in_use) <= 1.25 * min(in_use),
                 f"per-device bytes_in_use are not alike: {in_use}")
    report["preflight"] = dp.preflight_report
    del dp, placed

    single = build("data:1", 0.0)
    one_losses, one_walls, _ = run(single, batches)
    del single
    report["losses"] = {wide: dp_losses, "data:1": one_losses}
    report["step_wall_s"] = {wide: dp_walls, "data:1": one_walls}
    report["rtol"] = MULTICHIP_RTOL
    _require(all(math.isfinite(x) for x in dp_losses + one_losses),
             f"non-finite loss: {report['losses']}")
    _require(len(set(dp_losses)) == 3, f"nothing updated: {dp_losses}")
    _require(bool(np.allclose(dp_losses, one_losses, rtol=MULTICHIP_RTOL,
                              atol=0.0)),
             f"{wide} and data:1 loss trajectories disagree beyond "
             f"{MULTICHIP_RTOL}: {report['losses']}")

    # finding, not asserted: are the kernels' dropout masks mesh-invariant?
    drop = {}
    for spec in (wide, "data:1"):
        trainer = build(spec, 0.1)
        drop[spec] = run(trainer, batches[:1])[0][0]
        del trainer
    report["attention_dropout_step"] = {
        "losses": drop,
        "same_within_rtol": bool(np.isclose(
            drop[wide], drop["data:1"], rtol=MULTICHIP_RTOL, atol=0.0)),
    }
    report["attention"] = _attention_report(
        on_chip, int(batches[0][0]["input_ids"].shape[-1]),
        {"fused_fwd_lse", "fused_bwd"})


# -- the parent ----------------------------------------------------------------

def _child(fn, conn, kwargs) -> None:
    conn.send(fn(**kwargs))
    conn.close()


def run_phase(fn, *, drive=None, timeout_s: float = 1100.0, **kwargs) -> dict:
    """Run ``fn(**kwargs)`` in a spawned child (which owns the chip until it
    exits) and return its report. ``drive(proc)`` runs here meanwhile (the
    serve client). Any failure of the child is a failure of the run."""
    ctx = multiprocessing.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_child, args=(fn, send, kwargs))
    proc.start()
    send.close()
    try:
        extra = drive(proc) if drive is not None else {}
        if not recv.poll(timeout_s):
            raise SmokeFailure(f"{fn.__name__}: no report in {timeout_s}s")
        try:
            report = recv.recv()
        except EOFError:
            proc.join(30)
            raise SmokeFailure(
                f"{fn.__name__} failed (exit code {proc.exitcode}); its "
                f"traceback is above") from None
        proc.join(60)
        _require(proc.exitcode == 0,
                 f"{fn.__name__} exited with {proc.exitcode}")
        return {**report, **extra}
    finally:
        if proc.is_alive():
            proc.kill()
            proc.join()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the data:4-against-data:1 phase")
    args = parser.parse_args(argv)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        if args.chips == 4:
            phases = [dict(fn=multichip_phase, seed=args.seed,
                           work=work / "multichip")]
        else:
            phases = [
                dict(fn=parity_phase, seed=args.seed),
                dict(fn=train_phase, seed=args.seed, work=work / "train"),
                dict(fn=serve_child, seed=args.seed, work=work / "serve",
                     drive=lambda proc: serve_client(
                         proc, work / "serve", args.seed)),
            ]
        for phase in phases:
            report = run_phase(**phase)
            print(json.dumps(report, default=str), flush=True)
    _require(report["device"]["count"] == args.chips,
             f"ran on {report['device']['count']} device(s), "
             f"--chips {args.chips} was asked")
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

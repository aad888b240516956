"""The train runner: the recipe's job as ``cli.train`` composes it
(``compose.init_*`` -> ``Trainer`` -> ``Trainer.train``), measured from the
outside.

One call of ``Trainer.train`` runs every stretch as an epoch of its own; the
hooks it calls after each epoch open and close the stretches, and the loader,
re-classed on the instance (the trainer's ``isinstance`` checks still hold),
ends an epoch at a deadline or a batch count:

    epoch 1   warm-up: a few real batches, then one batch of every bucket
              shape the real ones did not reach;
    epoch 2   ``--trace 0``: the measured window of ``--seconds``.
              ``--trace 1``: a plain stretch (half the seconds) for the
              token rate (left out where ``plain_stretch`` is false: a cell
              the loader bounds takes the rate from the traced stretch), then
    epoch 3   a few steps under ``jax.profiler`` (telemetry still off), then
    epoch 4   a stretch with ``TrainTelemetry`` on for the host-clock
              partition; it blocks after every step, so its numbers are
              per-layer only.

Tokens are the non-pad tokens of the batches the loader handed over between
``global_step`` at open and at close; the clock stops on
``block_until_ready`` of the last step's outputs.
"""

from __future__ import annotations

import contextlib
import math
import os
import shutil
import time
from pathlib import Path

import numpy as np

from ..harness import checks, device, profiler, reference, textgen
from ..harness import trace_reduce
from ..harness.compile_watch import CompileWatch
from ..harness.manifest import CACHE_DIR, ROOT, Cell, write_cfg
from ..harness.result import end_to_end, last_line, note, read_per_layer

SHIPPED_CFG = ROOT / "config" / "test_bert.cfg"
# The LR schedule's length (epochs x steps) is a constant of the step program:
# the trainer is built for this many epochs whatever the run then asks of it,
# or a --trace 1 run (four stretches) would compile another program than a
# --trace 0 run (two) and find nothing in the cache (seen on the chip, PR 22).
SCHEDULE_EPOCHS = 4


# -- the job, as data ------------------------------------------------------------

def write_job_cfg(work: Path, seed: int, job: dict, model: str) -> Path:
    """``config/test_bert.cfg`` with the job's ``flags`` laid over it."""
    return write_cfg(SHIPPED_CFG, {
        **job["flags"],
        "model": model, "seed": seed,
        "vocab_file": work / "vocab.txt",
        "dump_dir": work / "results",
        "experiment_name": "bench",
        "data_path": work / "corpus.jsonl",
        "processed_data_path": work / "processed",
    }, work / "job.cfg")


# -- the loader's leash ----------------------------------------------------------

class Stretch:
    """One epoch's rule for ending, and what it handed over."""

    def __init__(self, *, seconds=None, batches=None, min_batches=0,
                 every_shape=False, annotate=False):
        self.seconds, self.batches = seconds, batches
        self.min_batches, self.every_shape = min_batches, every_shape
        self.annotate = annotate
        self.real_tokens = 0
        self.all_tokens = 0
        self.rows = 0
        self.handed = 0
        self.shapes: set = set()
        self.batch_shapes: list = []        # (rows, seq) of every batch
        self.name = None
        self.t_open = self.elapsed = None
        self.step_open = self.steps = 0

    def open(self) -> None:
        self.t_open = time.perf_counter()

    def over(self) -> bool:
        if self.handed < self.min_batches:
            return False
        if self.batches is not None and self.handed >= self.batches:
            return True
        return (self.seconds is not None
                and time.perf_counter() - self.t_open >= self.seconds)

    def count(self, batch) -> None:
        inputs = batch.inputs if hasattr(batch, "inputs") else batch[0]
        mask = np.asarray(inputs["attention_mask"])
        self.real_tokens += int(mask.sum())
        self.all_tokens += int(mask.size)
        self.rows += int(mask.shape[0])
        self.shapes.add(tuple(mask.shape))
        self.batch_shapes.append(tuple(mask.shape))
        self.handed += 1


class Leash:
    """What the re-classed loader asks before each batch."""

    def __init__(self):
        self.stretch: Stretch = Stretch(batches=0)

    def iterate(self, inner, loader):
        stretch = self.stretch
        scope = (lambda: profiler.annotation("bench:loader_next")) \
            if stretch.annotate else contextlib.nullcontext
        try:
            while not stretch.over():
                with scope():
                    batch = next(inner, None)
                if batch is None:
                    break
                stretch.count(batch)
                yield batch
        finally:
            inner.close()
        if stretch.every_shape:
            yield from self._unseen_shapes(stretch, loader)

    @staticmethod
    def _unseen_shapes(stretch, loader):
        """One batch of every bucket shape the real batches did not reach,
        so that no bucket's program is first run inside a window. Shape-only
        rows, as the trainer's own per-bucket pre-flight uses."""
        from ml_recipe_tpu.data.bucketing import (
            BucketedBatch,
            synthetic_qa_batch,
        )

        for seq, rows in sorted(getattr(loader, "batch_sizes", {}).items()):
            if (rows, seq) in stretch.shapes:
                continue
            inputs, labels = synthetic_qa_batch(rows, seq)
            batch = BucketedBatch(inputs=inputs, labels=labels, seq=seq,
                                  real_rows=rows, rows=rows)
            stretch.count(batch)
            yield batch


def leash_loader(loader, leash: Leash) -> None:
    base = type(loader)

    class Leashed(base):
        def __iter__(self):
            yield from leash.iterate(super().__iter__(), self)

    Leashed.__name__, Leashed.__qualname__ = base.__name__, base.__qualname__
    loader.__class__ = Leashed


# -- building the job ------------------------------------------------------------

def build_trainer(cell: Cell, job: dict, work: Path, seed: int,
                  n_epochs: int, finite_tap):
    """``cli.train._run_instrumented`` without the observability plane, the
    checkpoint hooks and the eval pass."""
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
    from ml_recipe_tpu.data.bucketing import parse_length_buckets
    from ml_recipe_tpu.ops import aot, autotune
    from ml_recipe_tpu.parallel import ParallelPlan
    from ml_recipe_tpu.train import Trainer
    from ml_recipe_tpu.utils.seed import set_seed

    cfg_path = write_job_cfg(
        work, seed, job, job.get("model", cell.config["model"]))
    _, (params, model_params) = get_params(
        (get_trainer_parser, get_model_parser), ["-c", str(cfg_path)])
    params.n_jobs = max(1, min(params.n_jobs, (os.cpu_count() or 2) // 2))
    autotune.configure(enabled=params.autotune,
                       cache_dir=params.autotune_cache)
    aot.configure(
        enabled=params.aot_cache != "off",
        cache_dir=params.aot_cache if params.aot_cache not in (None, "off")
        else None,
        cache_bytes=params.aot_cache_bytes or None)
    plan = ParallelPlan.from_spec(job.get("mesh"))
    rng_pool = set_seed(seed)
    model, model_state, tokenizer = init_model(
        model_params, bpe_dropout=params.bpe_dropout, rng_seed=seed,
        mesh=plan.mesh)
    train_dataset, test_dataset, weights = init_datasets(
        params, tokenizer=tokenizer, clear=params.clear_processed,
        rng=rng_pool.host_rng("chunk_sampling"))
    if "rows" in job:           # ready-made rows: enough for any window
        train_dataset.dataset_len = int(job["rows"])
    if "split_cache_size" in job:
        train_dataset.cache_size = int(job["split_cache_size"])
    trainer = Trainer(
        model=model, params=model_state, loss=init_loss(params, weights),
        collate_fun=init_collate_fun(tokenizer, max_seq_len=params.max_seq_len),
        trainer_params=params, train_dataset=train_dataset,
        test_dataset=test_dataset,
        writer_dir=params.dump_dir / f"board/{params.experiment_name}",
        mesh=plan.mesh, n_epochs=n_epochs,
        train_batch_size=params.train_batch_size,
        test_batch_size=params.test_batch_size,
        batch_split=params.batch_split, n_jobs=params.n_jobs,
        warmup_coef=params.warmup_coef, max_grad_norm=params.max_grad_norm,
        train_weights=weights, drop_optimizer=params.drop_optimizer,
        debug=params.debug, seed=int(job.get("trainer_seed", seed)),
        optimizer_sharding=params.optimizer_sharding,
        shard_optimizer=params.shard_optimizer,
        hbm_preflight=params.hbm_preflight,
        length_buckets=parse_length_buckets(
            params.length_buckets, params.max_seq_len),
        sequence_packing=params.sequence_packing,
        device_prefetch=params.device_prefetch, log_every=params.log_every,
        on_train_metrics=finite_tap,
    )
    return trainer, params, plan


# -- correctness, outside every window ---------------------------------------------

def check_against_reference(trainer, cell: Cell, job: dict, params, seed: int,
                            single_device: bool) -> dict:
    """System logits and loss (the trainer's model and loss, dropout off)
    against the plain reference, on four ragged seeded rows (eight under a
    mesh) at the job's sequence length; under a mesh of several chips also
    the loss of eight full rows on the mesh against the same rows on one
    chip."""
    import jax

    cfg = cell.config if "model" not in job else job["reference_config"]
    seq = int(params.max_seq_len)
    lengths = [seq, (3 * seq) // 4, (2 * seq) // 5, max(8, seq // 7)]
    if not single_device:       # two rows a chip: the kernels' probe batch
        lengths = lengths * 2
    inputs, labels = checks.seeded_rows(seed, cfg["vocab_size"], seq, lengths)
    model, loss_fn = trainer.model, trainer.loss

    def system(p, inputs, labels):
        preds = model.apply({"params": p}, **inputs, deterministic=True)
        return preds, loss_fn(preds, labels)[0]

    with trainer.mesh:
        got, got_loss = jax.jit(system)(trainer.params, inputs, labels)
        got, got_loss = jax.device_get((got, got_loss))
    host_params = jax.device_get(trainer.params)
    ref = jax.jit(lambda p, i: reference.forward(p, cfg, **i))
    want = ref(host_params, inputs)
    want_loss = float(reference.loss(
        want, labels, smooth_alpha=float(params.smooth_alpha)))
    want = jax.device_get(want)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    tolerances = checks.logit_tolerances(
        host_params, int(cfg["num_hidden_layers"]))
    report = {
        "logit_abs_err": errors, "logit_tol": tolerances,
        "loss": float(got_loss), "reference_loss": want_loss,
        "loss_rtol": checks.LOSS_RTOL,
    }
    ok = (checks.within(errors, tolerances)
          and checks.close(float(got_loss), want_loss, checks.LOSS_RTOL))
    if not single_device:
        import dataclasses

        rows, row_labels = checks.seeded_rows(
            seed + 1, cfg["vocab_size"], seq, [seq] * 8)
        with trainer.mesh:
            mesh_loss = float(jax.jit(system)(
                trainer.params, rows, row_labels)[1])
        one = dataclasses.replace(model, mesh=None)

        def single(p, inputs, labels):
            preds = one.apply({"params": p}, **inputs, deterministic=True)
            return loss_fn(preds, labels)[0]

        chip0 = jax.devices()[0]
        one_loss = float(jax.jit(single)(
            jax.device_put(host_params, chip0),
            jax.device_put(rows, chip0), jax.device_put(row_labels, chip0)))
        report.update(mesh_loss=mesh_loss, one_chip_loss=one_loss,
                      mesh_rtol=checks.MESH_RTOL)
        ok = ok and checks.close(mesh_loss, one_loss, checks.MESH_RTOL)
    report["ok"] = bool(ok)
    return report


# -- the run -----------------------------------------------------------------------

def run(cell: Cell, **how) -> int:
    with CompileWatch() as watch:
        return measure(cell, watch, **how)


def measure(cell: Cell, watch: CompileWatch, *, seed: int, seconds: float,
            trace: bool, rehearse: bool, t_start: float) -> int:
    import jax

    from ml_recipe_tpu.utils.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    record = device.require_chips(cell.chips, rehearse=rehearse)
    job = cell.job(rehearse)
    work = CACHE_DIR / "work" / cell.name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)

    # inputs from the seed: vocabulary, and for document traffic the corpus
    t0 = time.perf_counter()
    words = textgen.write_vocab(
        work / "vocab.txt", seed, int(cell.config["vocab_size"]))
    corpus = None
    if "corpus" in job:
        corpus = textgen.write_nq_corpus(
            work / "corpus.jsonl", seed, words, job["corpus"])
    t_inputs = time.perf_counter() - t0

    losses_finite = [True]
    steps_tapped = [0]

    def finite_tap(meters, *, step):
        steps_tapped[0] += 1
        if steps_tapped[0] == 1:    # pre-flight, compile or cache read, step 1
            state["first_step_s"] = time.perf_counter() - leash.stretch.t_open
        if not math.isfinite(float(meters["loss"]())):
            losses_finite[0] = False

    stretches = ["warmup", "window"]
    if trace:       # a loader-bound cell takes its rate from the traced stretch
        stretches = ["warmup"] + ["plain"] * bool(job.get("plain_stretch", True)) \
            + ["traced", "telemetry"]
    trainer, params, plan = build_trainer(
        cell, job, work, seed, SCHEDULE_EPOCHS, finite_tap)
    trainer.n_epochs = len(stretches)
    leash = Leash()
    leash_loader(trainer.train_dataloader, leash)
    n_chips = int(np.prod(list(plan.mesh.shape.values()))) if hasattr(
        plan.mesh, "shape") else 1
    t_built = time.perf_counter()

    trace_dir = CACHE_DIR / "trace" / cell.name
    done: dict = {}
    state = {"setup_s": None, "setup_compile": None, "trace_file": None}

    def settle():
        jax.block_until_ready((trainer.params, trainer.opt_state))

    def begin(name: str) -> None:
        """Open stretch ``name``: everything before the clock starts."""
        if hasattr(trainer.train_dataset, "_cache"):
            trainer.train_dataset._cache.clear()    # the window starts cold
        if name == "warmup":
            stretch = Stretch(min_batches=int(job["warmup_batches"]),
                              batches=int(job["warmup_batches"]),
                              every_shape=True)
        elif name == "window":
            stretch = Stretch(seconds=seconds, min_batches=1)
        elif name == "plain":
            stretch = Stretch(seconds=seconds / 2, min_batches=1)
        elif name == "traced":
            stretch = Stretch(seconds=float(job["trace_seconds"]),
                              batches=int(job["trace_batches"]),
                              min_batches=1, annotate=True)
            profiler.start(trace_dir)
        else:
            from ml_recipe_tpu.train.telemetry import TrainTelemetry

            trainer.telemetry = TrainTelemetry()
            stretch = Stretch(seconds=seconds / 4,
                              min_batches=int(job.get("telemetry_batches", 2)))
        stretch.name = name
        stretch.step_open = trainer.global_step
        leash.stretch = stretch
        stretch.open()

    def after_epoch(epoch_i: int) -> None:
        settle()
        now = time.perf_counter()
        stretch = leash.stretch
        stretch.elapsed = now - stretch.t_open
        stretch.steps = trainer.global_step - stretch.step_open
        done[stretch.name] = stretch
        if stretch.name == "traced":
            state["trace_file"] = profiler.stop(trace_dir)
        if stretch.name == "warmup":
            state["setup_compile"] = watch.mark()
            state["setup_s"] = time.perf_counter() - t_start
        if epoch_i < len(stretches):
            begin(stretches[epoch_i])

    begin("warmup")
    trainer.train(after_epoch_funcs=[after_epoch])
    window_compiles = watch.since(state["setup_compile"])["programs"]
    peak_bytes = device.memory_peak_bytes(n_chips)

    # -- earlier lines: what the job chose and what the set-up cost
    seq = int(params.max_seq_len)
    note(run={"workload": cell.name, "seed": seed, "seconds": seconds,
              "trace": int(trace), "rehearse": rehearse},
         compile_cache=cache_dir, n_jobs=params.n_jobs,
         mesh=plan.describe(), batch_split=trainer.batch_split,
         preflight=trainer.preflight_report,
         bytes_limit=device.bytes_limit(),
         memory_stats=device.memory_stats())
    note(setup={"setup_s": state["setup_s"], "inputs_s": t_inputs,
                "build_s": t_built - t_start - t_inputs,
                "warmup_s": done["warmup"].elapsed,
                "compile": state["setup_compile"], "missed": watch.missed[:12],
                "first_step_s": state.get("first_step_s"), "corpus": corpus,
                "warmup_shapes": sorted(done["warmup"].shapes)})
    if corpus is not None:
        note(split_cache={"cache_size": trainer.train_dataset.cache_size,
                          "hit_rate": 0.0 if not trainer.train_dataset.cache_size
                          else None})
    try:
        from ml_recipe_tpu.ops import autotune

        decisions = autotune.get().session_summary()["decisions"]
        note(attention={k: (d["regime"], d["geometry"], d["source"])
                        for k, d in decisions.items() if f"|L{seq}|" in k})
    except Exception as e:  # noqa: BLE001 - a note, never a reason to fail
        note(attention=f"no autotune summary: {e}")

    # -- the numbers
    measured = done.get("window") or done.get("plain") or done["traced"]
    rate = measured.real_tokens / measured.elapsed / n_chips
    note(stretches={name: {
        "steps": s.steps, "handed": s.handed, "rows": s.rows,
        "real_tokens": s.real_tokens, "all_tokens": s.all_tokens,
        "elapsed_s": s.elapsed, "shapes": sorted(s.shapes)}
        for name, s in done.items()})
    counted = all(s.steps == s.handed for s in done.values())

    # -- correct: outside every window
    verdict = check_against_reference(
        trainer, cell, job, params, seed, single_device=n_chips == 1)
    note(reference_check=verdict)
    correct = (verdict["ok"] and losses_finite[0] and window_compiles == 0
               and counted and measured.steps > 0)
    note(correct={"reference": verdict["ok"], "losses_finite": losses_finite[0],
                  "window_compiles": window_compiles,
                  "steps_match_batches": counted})
    attempted = sum(s.steps for n, s in done.items() if n != "warmup")

    dev = dict(record, count=n_chips, memory_peak_bytes=peak_bytes)
    if rehearse:
        # the tests' CPU run: the plumbing, and no number under a metric's name
        note(rehearsal={"tokens": measured.real_tokens,
                        "steps": measured.steps,
                        "window_compiles": window_compiles,
                        "setup_s": state["setup_s"]})
        last_line(correct=correct, attempted=attempted, failed=0, metrics={},
                  device=dev)
        return 0

    rate_name = job["rate_metric"]
    if not trace:
        metrics = end_to_end(cell, {rate_name: rate,
                                    "setup_s": state["setup_s"]})
        last_line(correct=correct, attempted=attempted, failed=0,
                  metrics=metrics, device=dev)
        return 0

    tr = trace_reduce.load(state["trace_file"], window_from="modules")
    busy = trace_reduce.busy_idle(tr)
    if busy is None:
        raise RuntimeError("the traced stretch shows no device operation")
    ctx = {
        "cell": cell, "device": dev, "peaks": device.peaks(record["kind"]),
        "trace": tr, "trace_steps": done["traced"].steps, "busy": busy,
        "trace_shapes": done["traced"].batch_shapes,
        "telemetry": trainer.telemetry.registry,
        "token_rate_chip": rate, "seq_len": seq,
        "micro_rows_chip": int(params.train_batch_size)
        // trainer.batch_split // n_chips,
        "stretch": measured, "chips": n_chips,
        "compile": {"setup": state["setup_compile"],
                    "window_compiles": window_compiles},
        "memory_peak_bytes": peak_bytes, "train": True,
    }
    dev.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
    note(trace={"file": state["trace_file"], "steps": done["traced"].steps,
                "busy": busy, "collectives": trace_reduce.collectives(tr)})
    last_line(correct=correct, attempted=attempted, failed=0,
              metrics=read_per_layer(cell, ctx), device=dev,
              breakdown=trace_reduce.breakdown(tr))
    return 0

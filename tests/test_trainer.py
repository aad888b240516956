"""Trainer runtime tests on the virtual 8-device CPU mesh.

Covers the SURVEY.md §7 minimum end-to-end slice: DummyDataset + fixed-shape
collate + tiny QA model + WeightedLoss + jitted SPMD train step with gradient
accumulation, eval with callbacks, and checkpoint save/load round-trip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ml_recipe_tpu.data.collate import make_collate_fun
from ml_recipe_tpu.data.datasets import DummyDataset
from ml_recipe_tpu.losses import build_loss
from ml_recipe_tpu.models import EncoderConfig, QAModel
from ml_recipe_tpu.parallel import build_mesh
from ml_recipe_tpu.train import (
    AccuracyCallback,
    MAPCallback,
    SaveBestCallback,
    Trainer,
)

from helpers import make_tokenizer

TINY = EncoderConfig(
    vocab_size=50,
    hidden_size=16,
    num_layers=2,
    num_heads=2,
    intermediate_size=32,
    max_position_embeddings=64,
    num_labels=5,
)

MAX_SEQ_LEN = 48
MAX_Q_LEN = 12


class TP:
    """Tiny trainer-params namespace (subset of get_trainer_parser flags)."""

    loss = "ce"
    smooth_alpha = 0.01
    focal_alpha = 1
    focal_gamma = 2
    w_start = 1
    w_end = 1
    w_start_reg = 0.5
    w_end_reg = 0.5
    w_cls = 1
    lr = 1e-3
    weight_decay = 0.01
    warmup_coef = 0.1
    optimizer = "adam"
    finetune = False
    best_metric = "map"
    best_order = ">"


def _make_trainer(tmp_path, *, batch_split=1, n_epochs=1, debug=False,
                  train_len=32, test_len=10, dropout=0.1, tp_cls=TP,
                  mesh_spec="data:8", attention_impl="xla", ln_impl="xla",
                  max_seq_len=MAX_SEQ_LEN, train_batch_size=16,
                  train_weights=None, cfg_overrides=None, **trainer_extra):
    tokenizer = make_tokenizer(tmp_path)
    rng = np.random.default_rng(0)
    train_ds = DummyDataset(
        tokenizer=tokenizer, max_seq_len=max_seq_len, max_question_len=MAX_Q_LEN,
        dataset_len=train_len, rng=rng,
    )
    test_ds = DummyDataset(
        tokenizer=tokenizer, max_seq_len=max_seq_len, max_question_len=MAX_Q_LEN,
        dataset_len=test_len, rng=rng,
    )

    cfg = EncoderConfig(
        vocab_size=len(tokenizer), hidden_size=16, num_layers=2, num_heads=2,
        intermediate_size=32, max_position_embeddings=max_seq_len + 2, num_labels=5,
        hidden_dropout_prob=dropout, attention_probs_dropout_prob=dropout,
    )
    if cfg_overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = build_mesh(mesh_spec)
    model = QAModel(cfg, attention_impl=attention_impl, mesh=mesh,
                    ln_impl=ln_impl)
    sample = train_ds[0]
    # init through the XLA-attention twin: params are impl-independent, and
    # ring's shard_map cannot shard the [1, L] init batch over the data axis
    params = QAModel(cfg).init(
        jax.random.key(0),
        np.asarray(sample.input_ids, dtype=np.int32)[None, :],
    )["params"]

    trainer = Trainer(
        model=model,
        params=params,
        loss=build_loss(tp_cls(), train_weights),
        collate_fun=make_collate_fun(tokenizer, max_seq_len=max_seq_len),
        trainer_params=tp_cls(),
        train_dataset=train_ds,
        test_dataset=test_ds,
        mesh=mesh,
        n_epochs=n_epochs,
        train_batch_size=train_batch_size,
        test_batch_size=8,
        batch_split=batch_split,
        n_jobs=2,
        warmup_coef=TP.warmup_coef,
        max_grad_norm=1.0,
        debug=debug,
        seed=0,
        **trainer_extra,
    )
    return trainer, tmp_path


def _param_snapshot(params):
    return jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), params)


def test_train_updates_params_and_steps(tmp_path):
    trainer, _ = _make_trainer(tmp_path)
    before = _param_snapshot(trainer.params)
    trainer.train()
    after = _param_snapshot(trainer.params)

    assert trainer.global_step == len(trainer.train_dataloader)
    changed = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, b), before, after
    )
    assert any(jax.tree_util.tree_leaves(changed)), "params did not update"


def test_grad_accumulation_matches_single_step(tmp_path):
    """batch_split must not change the optimizer trajectory (same global
    batch, same data order): reference semantics trainer.py:197-204."""
    # both trainers init from jax.random.key(0) -> identical starting params;
    # dropout off: micro-batches draw different dropout keys by design, the
    # equivalence is only exact deterministically (labels are all valid here,
    # so per-micro-batch CE normalization matches the global mean too)
    t1, _ = _make_trainer(tmp_path, batch_split=1, dropout=0.0)
    t2, _ = _make_trainer(tmp_path, batch_split=2, dropout=0.0)

    t1.train()
    t2.train()

    a = jax.tree_util.tree_leaves(_param_snapshot(t1.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t2.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


def test_test_loop_with_callbacks(tmp_path):
    trainer, _ = _make_trainer(tmp_path)
    metrics = trainer.test(
        0,
        callbacks=[
            MAPCallback(["yes", "no", "short", "long", "unknown"]),
            AccuracyCallback(),
        ],
    )
    assert "loss" in metrics
    assert "map" in metrics
    assert "c_acc" in metrics
    assert 0 <= metrics["c_acc"] <= 1


def test_checkpoint_roundtrip(tmp_path):
    trainer, _ = _make_trainer(tmp_path)
    trainer.train()
    step = trainer.global_step
    ckpt = tmp_path / "last.ch"
    trainer.save_state_dict(ckpt)
    assert ckpt.exists()

    (tmp_path / "t2").mkdir()
    trainer2, _ = _make_trainer(tmp_path / "t2")
    trainer2.load_state_dict(ckpt)
    assert trainer2.global_step == step
    for x, y in zip(
        jax.tree_util.tree_leaves(_param_snapshot(trainer.params)),
        jax.tree_util.tree_leaves(_param_snapshot(trainer2.params)),
    ):
        np.testing.assert_allclose(x, y, rtol=1e-6)

    # drop_optimizer restores weights only (reference trainer.py:395-403)
    (tmp_path / "t3").mkdir()
    trainer3, _ = _make_trainer(tmp_path / "t3")
    trainer3.drop_optimizer = True
    trainer3.load_state_dict(ckpt)
    assert trainer3.global_step == step


def test_debug_mode_breaks_after_one_step(tmp_path):
    trainer, _ = _make_trainer(tmp_path, debug=True)
    assert trainer.n_epochs == 2  # debug truncates epochs (trainer.py:147-148)
    trainer.train()
    assert trainer.global_step == 2  # one optimizer step per epoch

    # debug skips checkpoint writes (trainer.py:359-361)
    ckpt = tmp_path / "debug.ch"
    trainer.save_state_dict(ckpt)
    assert not ckpt.exists()


def test_save_best_callback(tmp_path):
    trainer, _ = _make_trainer(tmp_path)

    class P:
        best_metric = "map"
        best_order = ">"
        dump_dir = tmp_path
        experiment_name = "exp"

    cb = SaveBestCallback(P())
    trainer.test(0, callbacks=[cb, MAPCallback(["a", "b", "c", "d", "e"])])
    # MAPCallback runs after SaveBest in this order; run again so map exists
    metrics = trainer.test(
        0, callbacks=[MAPCallback(["a", "b", "c", "d", "e"]), cb]
    )
    if not np.isnan(metrics.get("map", np.nan)):
        assert (tmp_path / "exp" / "best.ch").exists()


def test_zero_optimizer_sharding(tmp_path):
    """ZeRO-1: moment leaves land sharded over the data axis, training runs,
    and the trajectory matches the replicated-optimizer run."""
    from jax.sharding import NamedSharding

    t_ref, _ = _make_trainer(tmp_path, batch_split=2, dropout=0.0)
    t_zero, _ = _make_trainer(tmp_path, batch_split=2, dropout=0.0)
    # rebuild with sharding enabled (zero_min_size=0: the tiny model's leaves
    # are all below the production 16384 threshold)
    t_zero = Trainer(
        model=t_zero.model, params=t_zero.params, loss=t_zero.loss,
        collate_fun=t_zero.collate_fun, trainer_params=TP(),
        train_dataset=t_zero.train_dataset, test_dataset=t_zero.test_dataset,
        mesh=t_zero.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
        batch_split=2, n_jobs=2, warmup_coef=TP.warmup_coef, max_grad_norm=1.0,
        seed=0, shard_optimizer=True, zero_min_size=0,
    )

    # at least one moment leaf must actually be sharded (not fully replicated)
    sharded = []
    for leaf in jax.tree_util.tree_leaves(t_zero.opt_state):
        if hasattr(leaf, "sharding") and leaf.ndim >= 1 and leaf.size >= 8:
            shard_shape = leaf.sharding.shard_shape(leaf.shape)
            sharded.append(int(np.prod(shard_shape)) < leaf.size)
    assert any(sharded), "no optimizer-state leaf is sharded over the mesh"

    t_ref.train()
    t_zero.train()

    a = jax.tree_util.tree_leaves(_param_snapshot(t_ref.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t_zero.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


def test_zero_checkpoint_roundtrip(tmp_path):
    t, _ = _make_trainer(tmp_path, dropout=0.0)
    t = Trainer(
        model=t.model, params=t.params, loss=t.loss, collate_fun=t.collate_fun,
        trainer_params=TP(), train_dataset=t.train_dataset,
        test_dataset=t.test_dataset, mesh=t.mesh, n_epochs=1,
        train_batch_size=16, test_batch_size=8, batch_split=1, n_jobs=2,
        warmup_coef=TP.warmup_coef, max_grad_norm=1.0, seed=0,
        shard_optimizer=True, zero_min_size=0,
    )
    t.train()
    ckpt = tmp_path / "zero.ch"
    t.save_state_dict(ckpt)

    t2, _ = _make_trainer(tmp_path, dropout=0.0)
    t2 = Trainer(
        model=t2.model, params=t2.params, loss=t2.loss, collate_fun=t2.collate_fun,
        trainer_params=TP(), train_dataset=t2.train_dataset,
        test_dataset=t2.test_dataset, mesh=t2.mesh, n_epochs=1,
        train_batch_size=16, test_batch_size=8, batch_split=1, n_jobs=2,
        warmup_coef=TP.warmup_coef, max_grad_norm=1.0, seed=0,
        shard_optimizer=True, zero_min_size=0,
    )
    t2.load_state_dict(ckpt)

    a = jax.tree_util.tree_leaves(_param_snapshot(t.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t2.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-6)
    # restored moments keep the ZeRO layout
    for l1, l2 in zip(
        jax.tree_util.tree_leaves(t.opt_state),
        jax.tree_util.tree_leaves(t2.opt_state),
    ):
        if hasattr(l1, "sharding"):
            assert l1.sharding.shard_shape(l1.shape) == l2.sharding.shard_shape(l2.shape)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """--sharded_checkpoint: per-process directory save of OWNED shards only
    (no gather), auto-detected on restore, exact state roundtrip with ZeRO
    sharding + dynamic loss scaling live (SURVEY §7 hard part (c))."""
    class TPLS(TP):
        apex_loss_scale = "dynamic"

    def build(src, sharded_save):
        return Trainer(
            model=src.model, params=src.params, loss=src.loss,
            collate_fun=src.collate_fun, trainer_params=TPLS(),
            train_dataset=src.train_dataset, test_dataset=src.test_dataset,
            mesh=src.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
            batch_split=1, n_jobs=2, warmup_coef=TP.warmup_coef,
            max_grad_norm=1.0, seed=0, shard_optimizer=True, zero_min_size=0,
            sharded_checkpoint=sharded_save,
        )

    t = build(_make_trainer(tmp_path, dropout=0.0)[0], True)
    t.train()
    ckpt = tmp_path / "sharded.ckpt"
    t.save_state_dict(ckpt)

    # directory layout: manifest + one shard file for this (single) process
    assert ckpt.is_dir()
    assert (ckpt / "manifest.msgpack").exists()
    shard_files = sorted(ckpt.glob("shard-*.msgpack"))
    assert len(shard_files) == 1

    # ZeRO-sharded moment leaves were written PIECEWISE (bounds smaller than
    # the full leaf), proving the no-gather property
    from flax import serialization

    shard_blob = serialization.msgpack_restore(shard_files[0].read_bytes())
    manifest = serialization.msgpack_restore(
        (ckpt / "manifest.msgpack").read_bytes()
    )
    assert int(shard_blob["global_step"]) == int(manifest["global_step"])
    piecewise = 0
    for key, pieces in shard_blob["shards"]["optimizer"].items():
        full = manifest["groups"]["optimizer"][key]["shape"]
        for p in pieces:
            if [b - a for a, b in p["bounds"]] != list(full):
                piecewise += 1
    assert piecewise > 0, "no optimizer leaf was written as sub-shards"

    t2 = build(_make_trainer(tmp_path, dropout=0.0)[0], False)
    t2.load_state_dict(ckpt)  # auto-detects the directory layout

    assert t2.global_step == t.global_step
    a = jax.tree_util.tree_leaves(_param_snapshot(t.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t2.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-6)
    for l1, l2 in zip(
        jax.tree_util.tree_leaves(t.opt_state),
        jax.tree_util.tree_leaves(t2.opt_state),
    ):
        np.testing.assert_allclose(
            np.asarray(l1), np.asarray(l2), rtol=1e-6,
            err_msg="optimizer/loss-scale state did not roundtrip",
        )
        if hasattr(l1, "sharding"):
            assert l1.sharding.shard_shape(l1.shape) == l2.sharding.shard_shape(l2.shape)

    # resumed trainer evaluates identically (fp tolerance: re-placed leaves
    # may carry a different GSPMD layout -> different reduction order)
    m1 = t.test(-1)
    m2 = t2.test(-1)
    if m1 is not None and m2 is not None:
        for k in m1:
            np.testing.assert_allclose(
                float(m1[k]), float(m2[k]), rtol=1e-4, atol=1e-6,
                err_msg=f"metric {k} diverged after sharded resume",
            )


# ---------------------------------------------------------------------------
# Async overlapped checkpointing (ISSUE 14)
# ---------------------------------------------------------------------------


def test_async_checkpoint_bytes_identical_to_sync(tmp_path):
    """ISSUE-14 acceptance: an --async_checkpoint save of a given step
    produces byte-identical checkpoint files to a sync save of the same
    state — the async path moves WHERE the serialize+write runs, never
    WHAT is written (single-file layout)."""
    t, _ = _make_trainer(tmp_path, dropout=0.0,
                         optimizer_sharding="zero1", zero_min_size=0)
    t.train()

    sync = tmp_path / "sync.ch"
    t.save_state_dict(sync)

    from ml_recipe_tpu.resilience.checkpoint_async import AsyncCheckpointer

    t.async_checkpoint = True
    t._async_ckpt = AsyncCheckpointer()
    async_path = tmp_path / "async.ch"
    t.save_state_dict(async_path)
    assert t._async_ckpt.pending() or async_path.exists()
    t.finish_pending_checkpoint()
    assert sync.read_bytes() == async_path.read_bytes(), (
        "async checkpoint bytes differ from a sync save of the same step"
    )


def test_async_checkpoint_sharded_manifest_identical_to_sync(tmp_path):
    """Sharded layout: manifest and shard files of an async save are
    byte-identical to a sync save of the same state (per-leaf crc32
    included — the background writer reuses the same persist helpers)."""
    t, _ = _make_trainer(tmp_path, dropout=0.0,
                         optimizer_sharding="zero1", zero_min_size=0,
                         sharded_checkpoint=True)
    t.train()

    sync = tmp_path / "sync.sck"
    t.save_state_dict(sync)

    from ml_recipe_tpu.resilience.checkpoint_async import AsyncCheckpointer

    t.async_checkpoint = True
    t._async_ckpt = AsyncCheckpointer()
    async_path = tmp_path / "async.sck"
    t.save_state_dict(async_path)
    t.finish_pending_checkpoint()

    names_sync = sorted(p.name for p in sync.iterdir())
    names_async = sorted(p.name for p in async_path.iterdir())
    assert names_sync == names_async
    for name in names_sync:
        assert (sync / name).read_bytes() == (async_path / name).read_bytes(), (
            f"sharded checkpoint file {name} differs between sync and "
            f"async saves"
        )


def test_async_checkpoint_roundtrip_with_bucketed_overlap(tmp_path):
    """Under ZeRO-1, train, save asynchronously (sharded layout), and
    restore into a fresh ZeRO-1 trainer — step, params and moment layouts
    all round-trip."""
    kw = dict(dropout=0.0, optimizer_sharding="zero1", zero_min_size=0,
              async_checkpoint=True, sharded_checkpoint=True)
    t, _ = _make_trainer(tmp_path, **kw)
    t.train()
    assert t.zero_enabled()
    ckpt = tmp_path / "both.sck"
    t.save_state_dict(ckpt)
    t.finish_pending_checkpoint()
    assert (ckpt / "manifest.msgpack").exists()

    (tmp_path / "t2").mkdir()
    t2, _ = _make_trainer(tmp_path / "t2", **kw)
    t2.load_state_dict(ckpt)
    assert t2.global_step == t.global_step
    for x, y in zip(
        jax.tree_util.tree_leaves(_param_snapshot(t.params)),
        jax.tree_util.tree_leaves(_param_snapshot(t2.params)),
    ):
        np.testing.assert_allclose(x, y, rtol=1e-6)
    # restored trainer keeps training (the donated-buffer resume path)
    t2.n_epochs = 1
    t2.train()
    assert t2.global_step > t.global_step


def test_async_checkpoint_blocking_time_beats_sync(tmp_path):
    """ISSUE-14 acceptance (CPU smoke): at the same state size, the
    critical-path (blocking) cost of an async save — the device->host
    snapshot — is >= 3x lower than a sync save's serialize+write. Pinned
    at the checkpoint-API level where the comparison is deterministic:
    both legs run on one host-resident state, so the ratio is pure
    snapshot-copy vs msgpack-serialize+write (the bench --mode train
    twins, checkpoint_blocking_ms / checkpoint_total_ms, report the same
    split through the live Trainer)."""
    import time as _time

    from ml_recipe_tpu.train.checkpoint import (
        persist_state,
        save_state_dict,
        snapshot_state,
    )

    rng = np.random.default_rng(0)
    # ~64 MB of state: large enough that serialize+write dwarfs the copy
    params = {f"w{i}": rng.standard_normal((1024, 2048)).astype(np.float32)
              for i in range(8)}

    def best_of(fn, n=3):
        return min(
            (lambda t0: (fn(), _time.perf_counter() - t0)[1])(
                _time.perf_counter()
            )
            for _ in range(n)
        )

    sync_s = best_of(
        lambda: save_state_dict(tmp_path / "sync.ch", params=params,
                                global_step=1)
    )
    blocking_s = best_of(
        lambda: snapshot_state(params=params, global_step=1, copy=True)
    )
    # the snapshot is a real copy (not a lazy view): persisting it after
    # the source mutates must still write the snapshotted values
    snap = snapshot_state(params=params, global_step=1, copy=True)
    params["w0"][:] = -1.0
    persist_state(tmp_path / "snap.ch", snap)
    from flax import serialization

    stored = serialization.msgpack_restore(
        (tmp_path / "snap.ch").read_bytes()
    )
    assert float(np.asarray(stored["model"]["w0"]).max()) > 0.0

    assert blocking_s * 3 <= sync_s, (
        f"async blocking leg {blocking_s * 1e3:.1f} ms is not >=3x below "
        f"the sync save {sync_s * 1e3:.1f} ms at the same state size"
    )


def test_async_checkpoint_persist_error_surfaces_at_barrier(tmp_path):
    """A failed background persist must raise AsyncCheckpointError at the
    next completion barrier — a run must not report success while its
    checkpoint silently failed to land."""
    import pytest

    from ml_recipe_tpu.resilience.checkpoint_async import (
        AsyncCheckpointError,
        AsyncCheckpointer,
    )

    ck = AsyncCheckpointer()

    def boom():
        raise OSError("disk full")

    ck.submit(tmp_path / "x.ch", boom)
    with pytest.raises(AsyncCheckpointError, match="disk full"):
        ck.wait()
    # the error is consumed by the strict barrier; the next wait is clean
    ck.wait()

    # raise_errors=False logs AND consumes: a stale failure (already
    # surfaced at ERROR) must not abort a later, unrelated save — the
    # SIGTERM emergency-checkpoint path depends on this
    ck.submit(tmp_path / "y.ch", boom)
    ck.wait(raise_errors=False)
    ck.wait()  # clean: the best-effort barrier consumed the error


def test_async_checkpoint_on_done_reports_stall(tmp_path):
    """on_done receives (persist_s, stalled_s): the share of the persist
    the main thread spent blocked in wait() is reported separately, so
    the ledger books only the genuinely overlapped remainder — a stalled
    wait must not be double-counted as overlap."""
    import threading

    from ml_recipe_tpu.resilience.checkpoint_async import AsyncCheckpointer

    ck = AsyncCheckpointer()
    got = []
    gate = threading.Event()
    ck.submit(
        tmp_path / "s.ch", lambda: gate.wait(timeout=10),
        on_done=lambda persist_s, stalled_s: got.append(
            (persist_s, stalled_s)
        ),
    )
    release = threading.Timer(0.15, gate.set)
    release.start()
    ck.wait()  # blocks until the gated persist finishes -> stalled wait
    release.cancel()
    assert got, "on_done did not fire"
    persist_s, stalled_s = got[0]
    assert stalled_s > 0.05, "stalled wait time was not reported"
    assert persist_s >= stalled_s


def test_async_checkpoint_multihost_sharded_falls_back_to_sync(tmp_path):
    """Multi-host + --sharded_checkpoint: the sharded persist crosses
    process barriers (device collectives), which must never run on a
    background thread concurrently with training collectives — the save
    falls back to the sync path (logged), with the file complete the
    moment save_state_dict returns."""
    t, _ = _make_trainer(tmp_path, dropout=0.0, sharded_checkpoint=True,
                         async_checkpoint=True)
    t.train()
    t.process_count = 2  # simulate a multi-host world for the gate only
    assert not t._async_supported()
    ckpt = tmp_path / "fallback.sck"
    t.save_state_dict(ckpt)
    # sync fallback: complete on return, nothing pending in the executor
    assert (ckpt / "manifest.msgpack").exists()
    assert not t._async_ckpt.pending()


def test_async_checkpoint_single_flight_orders_saves(tmp_path):
    """submit() waits for the previous persist: two back-to-back saves to
    one path can never interleave their writes, and the LAST submitted
    state is what lands."""
    import threading

    from ml_recipe_tpu.resilience.checkpoint_async import AsyncCheckpointer

    ck = AsyncCheckpointer()
    order = []
    gate = threading.Event()

    def slow():
        gate.wait(timeout=10)
        order.append("first")

    def fast():
        order.append("second")

    ck.submit(tmp_path / "z.ch", slow)
    release = threading.Timer(0.2, gate.set)
    release.start()
    ck.submit(tmp_path / "z.ch", fast)  # must block until `slow` finished
    ck.wait()
    release.cancel()
    assert order == ["first", "second"]


def test_loss_scale_unit():
    from ml_recipe_tpu.train import loss_scale as ls

    st = ls.init_state(1024.0, dynamic=True)
    # overflow halves
    st2 = ls.update_state(st, jnp.asarray(False))
    assert float(st2.scale) == 512.0 and int(st2.growth_count) == 0
    # growth_interval consecutive finite steps double
    st3 = ls.init_state(1024.0, dynamic=True)
    for _ in range(2000):
        st3 = ls.update_state(st3, jnp.asarray(True))
    assert float(st3.scale) == 2048.0
    # static never adjusts
    st4 = ls.init_state(128.0, dynamic=False)
    assert float(ls.update_state(st4, jnp.asarray(False)).scale) == 128.0

    # masked_update keeps old values on overflow
    new = {"a": jnp.ones(3)}
    old = {"a": jnp.zeros(3)}
    kept = ls.masked_update(new, old, jnp.asarray(False))
    np.testing.assert_array_equal(np.asarray(kept["a"]), 0.0)


def test_static_loss_scale_matches_unscaled_trajectory(tmp_path):
    """Scaling the loss by S and unscaling grads by 1/S must not change the
    optimizer trajectory (f32 grads, no overflow at these magnitudes)."""

    class TPS(TP):
        apex_loss_scale = 128.0

    t_ref, _ = _make_trainer(tmp_path, dropout=0.0)
    t_s, _ = _make_trainer(tmp_path, dropout=0.0)
    t_s = Trainer(
        model=t_s.model, params=t_s.params, loss=t_s.loss,
        collate_fun=t_s.collate_fun, trainer_params=TPS(),
        train_dataset=t_s.train_dataset, test_dataset=t_s.test_dataset,
        mesh=t_s.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
        batch_split=1, n_jobs=2, warmup_coef=TP.warmup_coef,
        max_grad_norm=1.0, seed=0,
    )
    assert isinstance(t_s.opt_state, tuple)  # (opt_state, ls_state) bundle

    t_ref.train()
    t_s.train()

    a = jax.tree_util.tree_leaves(_param_snapshot(t_ref.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t_s.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


def test_global_batch_stats_are_cross_replica(tmp_path):
    """The sync_bn parity claim: a batch-mean computed under jit on a
    data-sharded global array equals the mean over the FULL global batch."""
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.parallel.sharding import make_global_array

    mesh = build_mesh("data:8")
    x = np.random.default_rng(0).normal(size=(32, 6)).astype(np.float32)
    with mesh:
        gx = make_global_array({"x": x}, mesh)["x"]
        mean = jax.jit(lambda a: a.mean(axis=0))(gx)
    np.testing.assert_allclose(np.asarray(mean), x.mean(axis=0), rtol=1e-6)


def test_loss_scale_checkpoint_compatible_across_flag_change(tmp_path):
    """A checkpoint saved WITHOUT loss scaling must load into a run WITH it
    (and vice versa): ls state lives under its own checkpoint key."""

    class TPS(TP):
        apex_loss_scale = "dynamic"

    def make(tp_cls, sub):
        t, _ = _make_trainer(tmp_path, dropout=0.0)
        return Trainer(
            model=t.model, params=t.params, loss=t.loss,
            collate_fun=t.collate_fun, trainer_params=tp_cls(),
            train_dataset=t.train_dataset, test_dataset=t.test_dataset,
            mesh=t.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
            batch_split=1, n_jobs=2, warmup_coef=TP.warmup_coef,
            max_grad_norm=1.0, seed=0,
        )

    plain = make(TP, "a")  # sub tags kept for readability only
    plain.train()
    ck_plain = tmp_path / "plain.ch"
    plain.save_state_dict(ck_plain)

    scaled = make(TPS, "b")
    scaled.load_state_dict(ck_plain)  # plain ckpt -> scaled run: ls kept fresh
    assert scaled.global_step == plain.global_step
    _, ls = scaled._split_ls()
    assert ls is not None and float(ls.scale) == 2.0 ** 15

    scaled.train()
    ck_scaled = tmp_path / "scaled.ch"
    scaled.save_state_dict(ck_scaled)

    plain2 = make(TP, "c")
    plain2.load_state_dict(ck_scaled)  # scaled ckpt -> plain run: ls ignored
    assert plain2.global_step == scaled.global_step

    scaled2 = make(TPS, "d")
    scaled2.load_state_dict(ck_scaled)  # scaled -> scaled: ls restored
    _, ls2 = scaled2._split_ls()
    # growth_count counts only the steps trained UNDER scaling (the ls state
    # was fresh when the plain checkpoint was loaded)
    assert int(ls2.growth_count) == scaled.global_step - plain.global_step


def test_loss_scale_min_floor():
    from ml_recipe_tpu.train import loss_scale as ls

    st = ls.init_state(2.0 ** -13, dynamic=True)
    for _ in range(10):  # sustained overflow burst
        st = ls.update_state(st, jnp.asarray(False))
    assert float(st.scale) == 2.0 ** -14  # floored, never 0


def test_loss_scale_mode_mismatch_keeps_configured(tmp_path):
    """--apex_loss_scale is config: resuming a dynamic checkpoint into a
    static run must keep the configured static state (and vice versa)."""

    class TPD(TP):
        apex_loss_scale = "dynamic"

    class TPStatic(TP):
        apex_loss_scale = 64.0

    def make(tp_cls):
        t, _ = _make_trainer(tmp_path, dropout=0.0)
        return Trainer(
            model=t.model, params=t.params, loss=t.loss,
            collate_fun=t.collate_fun, trainer_params=tp_cls(),
            train_dataset=t.train_dataset, test_dataset=t.test_dataset,
            mesh=t.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
            batch_split=1, n_jobs=2, warmup_coef=TP.warmup_coef,
            max_grad_norm=1.0, seed=0,
        )

    dyn = make(TPD)
    dyn.train()
    ck = tmp_path / "dyn.ch"
    dyn.save_state_dict(ck)

    static = make(TPStatic)
    static.load_state_dict(ck)
    _, ls = static._split_ls()
    assert not bool(ls.dynamic)
    assert float(ls.scale) == 64.0  # configured static value, not the ckpt's
    assert static.global_step == dyn.global_step  # weights/step still restored


def test_legacy_clip_chain_checkpoint_loads(tmp_path):
    """Checkpoints saved when clip_by_global_norm lived in the optax chain
    (a leading EmptyState) must still resume after clipping moved into the
    train step."""
    from ml_recipe_tpu.train.optim import build_optimizer

    t, _ = _make_trainer(tmp_path, dropout=0.0)
    t.train()

    # forge a legacy checkpoint: same trained params, optimizer state saved
    # under the OLD chain structure (clip EmptyState + core)
    legacy_tx, _, _ = build_optimizer(
        TP(), t.params, num_training_steps=4, max_grad_norm=1.0,
        warmup_coef=TP.warmup_coef,
    )
    legacy_state = jax.jit(legacy_tx.init)(t.params)
    from ml_recipe_tpu.train import checkpoint as ck

    ck.save_state_dict(
        tmp_path / "legacy.ch", params=t.params, opt_state=legacy_state,
        global_step=t.global_step, is_primary=True,
    )

    t2, _ = _make_trainer(tmp_path, dropout=0.0)
    t2.load_state_dict(tmp_path / "legacy.ch")  # must not raise
    assert t2.global_step == t.global_step
    a = jax.tree_util.tree_leaves(_param_snapshot(t.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t2.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-6)


class FinetuneTP(TP):
    """Freeze everything but the classifier head (reference init.py:85-123)."""

    finetune = True
    finetune_transformer = False
    finetune_position = False
    finetune_position_reg = False
    finetune_class = True


def test_finetune_freezes_unselected_modules(tmp_path):
    """finetune_class=True must update ONLY the classifier head: frozen
    modules get zero updates (optax.masked passes raw grads through unless
    explicitly zeroed) and the clip norm is measured over trainable grads."""
    trainer, _ = _make_trainer(tmp_path, tp_cls=FinetuneTP, debug=True)
    before = _param_snapshot(trainer.params)
    trainer.train()
    after = _param_snapshot(trainer.params)

    for frozen_root in ("transformer", "position_outputs", "reg_start", "reg_end"):
        fa = jax.tree_util.tree_leaves(after[frozen_root])
        fb = jax.tree_util.tree_leaves(before[frozen_root])
        for x, y in zip(fb, fa):
            np.testing.assert_array_equal(
                x, y, err_msg=f"frozen module {frozen_root} drifted"
            )
    changed = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, b), before["classifier"], after["classifier"]
    )
    assert any(jax.tree_util.tree_leaves(changed)), "classifier did not train"


def test_tp_mesh_trains_with_tree_accumulation(tmp_path):
    """A model-axis mesh takes the sharding-preserving per-tensor gradient
    path (the flat-vector carry would all-gather TP-sharded grads); the
    trajectory must still match the data-only mesh run step for step."""
    t_tp, _ = _make_trainer(tmp_path, batch_split=2, dropout=0.0,
                            mesh_spec="data:4,model:2")
    t_dp, _ = _make_trainer(tmp_path, batch_split=2, dropout=0.0,
                            mesh_spec="data:8")
    t_tp.train()
    t_dp.train()
    assert t_tp.global_step == t_dp.global_step > 0
    a = jax.tree_util.tree_leaves(_param_snapshot(t_tp.params))
    b = jax.tree_util.tree_leaves(_param_snapshot(t_dp.params))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


def test_finetune_legacy_checkpoint_migrates(tmp_path):
    """Optimizer states saved under the old bare optax.masked(tx) chain (no
    trailing masked(set_to_zero)) must still load: they are wrapped as slot
    "0" of the new 2-element chain on restore."""
    from flax import serialization

    from ml_recipe_tpu.train import checkpoint as ck

    t, _ = _make_trainer(tmp_path, tp_cls=FinetuneTP, debug=True)
    t.train()
    # Emulate the legacy layout: element "0" of the new chain IS the old
    # masked(tx) state, so a legacy file carried exactly that subtree.
    new_sd = serialization.to_state_dict(t.opt_state)
    assert set(new_sd.keys()) == {"0", "1"}
    legacy_path = tmp_path / "legacy_ft.ch"
    ck.save_state_dict(
        legacy_path, params=t.params, opt_state=None,
        global_step=t.global_step, is_primary=True,
    )
    # splice the legacy optimizer subtree into the saved file
    import msgpack  # noqa: F401  (flax serialization uses msgpack natively)

    blob = serialization.msgpack_restore(legacy_path.read_bytes())
    blob["optimizer"] = new_sd["0"]
    legacy_path.write_bytes(serialization.msgpack_serialize(blob))

    t2, _ = _make_trainer(tmp_path, tp_cls=FinetuneTP, debug=True)
    t2.load_state_dict(legacy_path)  # must not raise
    assert t2.global_step == t.global_step


def test_trace_writes_xplane_steady_state(tmp_path):
    """trace_dir dumps a device profile of the steady-state steps 2-4
    (SURVEY.md §5 tracing parity: the reference had only wall-time
    logging). 80 samples / batch 16 = 5 steps, so the documented capture
    window (not the short-epoch fallback) is exercised."""
    trainer, _ = _make_trainer(tmp_path, train_len=80)
    trainer.trace_dir = tmp_path / "trace"
    trainer.train()
    dumped = list((tmp_path / "trace").rglob("*.xplane.pb"))
    assert dumped, "no xplane profile written for the steady-state window"


def test_sharded_checkpoint_tp_mesh_roundtrip(tmp_path):
    """Sharded save with MODEL-axis (TP) sharded params: the encoder's
    tensor-parallel leaves are written piecewise by their owners and must
    reassemble exactly on restore."""
    src, _ = _make_trainer(tmp_path, dropout=0.0, mesh_spec="data:4,model:2")

    # local builder (not _make_trainer) because the restore-side trainer must
    # start from DIFFERENT params (fresh key-1 init) — retention must not be
    # able to masquerade as restoration, and _make_trainer always inits key 0
    def build(params):
        return Trainer(
            model=src.model, params=params, loss=src.loss,
            collate_fun=src.collate_fun, trainer_params=TP(),
            train_dataset=src.train_dataset, test_dataset=src.test_dataset,
            mesh=src.mesh, n_epochs=1, train_batch_size=16, test_batch_size=8,
            batch_split=1, n_jobs=2, warmup_coef=TP.warmup_coef,
            max_grad_norm=1.0, seed=0, sharded_checkpoint=True,
        )

    t = build(src.params)
    t.train()
    trained = _param_snapshot(t.params)
    ckpt = tmp_path / "tp_sharded.ckpt"
    t.save_state_dict(ckpt)
    assert ckpt.is_dir()

    # at least one param leaf must have been written as sub-shards (TP
    # shards the encoder weights over the model axis)
    from flax import serialization

    blob = serialization.msgpack_restore(
        (ckpt / "shard-00000.msgpack").read_bytes()
    )
    manifest = serialization.msgpack_restore(
        (ckpt / "manifest.msgpack").read_bytes()
    )
    piecewise = 0
    for key, pieces in blob["shards"]["model"].items():
        full = manifest["groups"]["model"][key]["shape"]
        for p in pieces:
            if [b - a for a, b in p["bounds"]] != list(full):
                piecewise += 1
    assert piecewise > 0, "no TP-sharded param leaf was written piecewise"

    fresh = src.model.init(
        jax.random.key(1),
        np.zeros((1, 8), np.int32),
    )["params"]
    t2 = build(fresh)
    t2.load_state_dict(ckpt)
    for a, b in zip(
        jax.tree_util.tree_leaves(trained),
        jax.tree_util.tree_leaves(_param_snapshot(t2.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_sharded_save_interrupted_swap_recovery(tmp_path):
    """A sharded save that dies between the swap's two renames leaves no
    checkpoint at the live path; both the next load AND the next save must
    roll the staged/old sibling forward or back instead of treating it as
    deletable debris (round-3 review finding)."""
    import os
    import shutil

    t, _ = _make_trainer(tmp_path, dropout=0.0)
    t.sharded_checkpoint = True
    t.train()
    ckpt = tmp_path / "swap.ckpt"
    t.save_state_dict(ckpt)
    want = _param_snapshot(t.params)

    def fresh():
        (tmp_path / "fresh").mkdir(exist_ok=True)
        t2, _ = _make_trainer(tmp_path / "fresh", dropout=0.0)
        t2.sharded_checkpoint = True
        return t2

    # crash AFTER rename(path -> old), BEFORE rename(staging -> path), with
    # the staged save COMPLETE (manifest written last => present): roll
    # forward to the staged checkpoint
    shutil.copytree(ckpt, str(ckpt) + ".saving")
    os.rename(ckpt, str(ckpt) + ".old")
    t2 = fresh()
    t2.load_state_dict(ckpt)
    assert t2.global_step == t.global_step
    for a, b in zip(
        jax.tree_util.tree_leaves(want),
        jax.tree_util.tree_leaves(_param_snapshot(t2.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert ckpt.is_dir() and not os.path.exists(str(ckpt) + ".saving")
    # load-side recovery restores the live path only; the stale .old is the
    # next save's to clean
    shutil.rmtree(str(ckpt) + ".old")

    # crash BEFORE the staged manifest landed: only the old checkpoint is
    # complete -> roll back to it
    (ckpt / "manifest.msgpack").rename(tmp_path / "stash.msgpack")
    os.rename(ckpt, str(ckpt) + ".saving")  # incomplete staging
    shutil.copytree(str(ckpt) + ".saving", str(ckpt) + ".old")
    (tmp_path / "stash.msgpack").rename(
        str(ckpt) + ".old/manifest.msgpack"
    )
    t3 = fresh()
    t3.load_state_dict(ckpt)
    assert t3.global_step == t.global_step

    # and the next SAVE after such a crash recovers first, then overwrites
    os.rename(ckpt, str(ckpt) + ".old")
    t3.save_state_dict(ckpt)
    assert ckpt.is_dir() and (ckpt / "manifest.msgpack").exists()
    assert not os.path.exists(str(ckpt) + ".old")
    assert not os.path.exists(str(ckpt) + ".saving")


# -- HBM pre-flight planner (ISSUE 2) ----------------------------------------


class _FakeMemoryAnalysis:
    """memory_analysis double: temp bytes shrink as batch_split grows —
    the shape of the real activation-memory curve under accumulation."""

    def __init__(self, split):
        self.argument_size_in_bytes = 1_000
        self.output_size_in_bytes = 500
        self.temp_size_in_bytes = 8_000 // split
        self.alias_size_in_bytes = 500


class _FakeCompiled:
    def __init__(self, split):
        self._split = split

    def memory_analysis(self):
        return _FakeMemoryAnalysis(self._split)


def _fake_compile_fn(compiles):
    def compile_fn(trainer):
        compiles.append(trainer.batch_split)
        return _FakeCompiled(trainer.batch_split)
    return compile_fn


def test_hbm_preflight_raises_batch_split(tmp_path):
    """Acceptance (ISSUE 2): given a step whose memory_analysis exceeds
    device HBM, the pre-flight raises batch_split and proceeds — instead
    of surfacing an XLA OOM — and the report carries before/after bytes."""
    trainer, _ = _make_trainer(tmp_path, batch_split=1)
    compiles = []
    # split 1 needs 1000+500+8000-500 = 9000 > 5000; split 2 needs 5000 <= 5000
    report = trainer.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn(compiles), limit_bytes=5_000,
    )
    assert trainer.batch_split == 2
    assert compiles == [1, 2]  # re-planned once, at the raised split
    assert report["applied"] is True
    assert report["batch_split_before"] == 1 and report["batch_split"] == 2
    assert report["bytes_before"] == 9_000 and report["bytes"] == 5_000
    assert report["limit_bytes"] == 5_000
    assert trainer.preflight_report is report
    # the jitted step was rebuilt for the new split and is ready to run
    assert trainer._jit_train_step is not None
    assert trainer._preflight_done


def test_hbm_preflight_noop_within_limit(tmp_path):
    """A configuration that already fits leaves batch_split untouched and
    compiles exactly once (the compile is also the first step's)."""
    trainer, _ = _make_trainer(tmp_path, batch_split=2)
    compiles = []
    report = trainer.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn(compiles), limit_bytes=10_000,
    )
    assert trainer.batch_split == 2 and compiles == [2]
    assert report["applied"] is False
    assert report["bytes"] == report["bytes_before"] == 5_000


def test_hbm_preflight_stops_at_mesh_divisibility(tmp_path):
    """batch_split can only rise while the micro-batch still divides over
    the mesh data axis (batch 16 over data:8 caps the split at 2); past
    that the planner logs and proceeds — XLA gets the final word."""
    trainer, _ = _make_trainer(tmp_path, batch_split=1)
    compiles = []
    report = trainer.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn(compiles), limit_bytes=1_000,
    )
    # walked 1 -> 2, then no legal split remains (4 would leave micro 4 on
    # the 8-wide data axis); still over limit but proceeds
    assert trainer.batch_split == 2 and compiles == [1, 2]
    assert report["applied"] is True and report["bytes"] == 5_000


def test_hbm_preflight_disabled_or_no_limit(tmp_path):
    """hbm_preflight=False (or a backend with no memory limit, e.g. CPU)
    must be a clean no-op."""
    trainer, _ = _make_trainer(tmp_path, batch_split=1, hbm_preflight=False)
    assert trainer._preflight_done  # the train loop will not re-plan
    assert trainer.preflight_train_step(None, None) is None
    assert trainer.batch_split == 1

    trainer2, _ = _make_trainer(tmp_path, batch_split=1)
    assert not trainer2._preflight_done
    # CPU devices report no bytes_limit -> planner stands down
    assert trainer2.preflight_train_step(None, None) is None
    assert trainer2.batch_split == 1 and trainer2._preflight_done


# -- padding-free input pipeline (ISSUE 4) ------------------------------------


def test_bucketed_training_runs_and_updates_params(tmp_path):
    """Bucketed path end-to-end on the 8-device mesh: params update, steps
    land, and the loader's padding accounting is populated."""
    trainer, _ = _make_trainer(tmp_path, length_buckets=[24, MAX_SEQ_LEN])
    before = _param_snapshot(trainer.params)
    trainer.train()
    after = _param_snapshot(trainer.params)
    assert trainer.global_step > 0
    changed = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, b), before, after
    )
    assert any(jax.tree_util.tree_leaves(changed)), "params did not update"
    stats = trainer.train_dataloader.epoch_stats
    assert stats and stats["batches"] == trainer.global_step


def test_flag_off_exactly_reproduces_default_path(tmp_path):
    """Acceptance: --length_buckets off / --device_prefetch 0 construct the
    plain DataLoader + synchronous placement and produce a bit-identical
    trajectory to a default-constructed trainer."""
    from ml_recipe_tpu.data.loader import DataLoader

    (tmp_path / "off").mkdir()
    t_off, _ = _make_trainer(
        tmp_path / "off", length_buckets=None, device_prefetch=0
    )
    assert isinstance(t_off.train_dataloader, DataLoader)
    (tmp_path / "default").mkdir()
    t_def, _ = _make_trainer(tmp_path / "default")
    t_off.train()
    t_def.train()
    for x, y in zip(
        jax.tree_util.tree_leaves(_param_snapshot(t_off.params)),
        jax.tree_util.tree_leaves(_param_snapshot(t_def.params)),
    ):
        np.testing.assert_array_equal(x, y)


def test_pad_last_rows_excluded_from_eval_metrics(tmp_path):
    """Regression (ISSUE 4 satellite): pad_last repetition rows of the final
    partial eval batch must be excluded from loss/metric averaging — the
    meter average must equal the mean over TRIMMED per-batch losses."""
    # 10 test items / batch 8 -> final batch has 2 real + 6 repeated rows
    trainer, _ = _make_trainer(tmp_path, dropout=0.0, test_len=10)
    assert trainer.test_dataloader.real_rows(0) == 8
    assert trainer.test_dataloader.real_rows(1) == 2

    metrics = trainer.test(0)

    # independent recompute: eval each padded batch, trim to real_rows,
    # and average per-batch losses weighted by REAL rows (pad rows carry
    # zero weight in the epoch mean)
    eval_step = trainer._build_eval_step()
    losses, weights = [], []
    with trainer.mesh:
        for i, (inputs, labels) in enumerate(trainer.test_dataloader):
            preds, _ = eval_step(
                trainer.params,
                trainer._global_batch(inputs),
                trainer._global_batch(labels),
            )
            n = trainer.test_dataloader.real_rows(i)
            preds = {k: jnp.asarray(np.asarray(v)[:n]) for k, v in preds.items()}
            labels = {k: jnp.asarray(np.asarray(v)[:n]) for k, v in labels.items()}
            _, values = trainer.loss(preds, labels)
            losses.append(float(values["loss"]))
            weights.append(n)
    assert weights == [8, 2]
    np.testing.assert_allclose(
        metrics["loss"], np.average(losses, weights=weights), rtol=1e-5
    )
    # sanity that the pad rows WOULD have moved the number (the recompute is
    # not vacuous): an untrimmed average differs
    assert trainer._test_sampler.pad_last


def test_bucketed_eval_trims_padded_tail_rows(tmp_path):
    """Bucketed eval: BucketedBatch.real_rows drives the same trimming —
    metrics must match a pad-to-max eval of the same model/data within fp
    tolerance (different batch shapes -> different reduction order)."""
    (tmp_path / "b").mkdir()
    t_b, _ = _make_trainer(
        tmp_path / "b", dropout=0.0, test_len=10,
        length_buckets=[MAX_SEQ_LEN],
    )
    (tmp_path / "p").mkdir()
    t_p, _ = _make_trainer(tmp_path / "p", dropout=0.0, test_len=10)
    m_b = t_b.test(0)
    m_p = t_p.test(0)
    for k in m_p:
        np.testing.assert_allclose(
            float(m_b[k]), float(m_p[k]), rtol=1e-4, atol=1e-6,
            err_msg=f"bucketed eval metric {k} diverged",
        )


def _fake_bucket_compile_fn(compiles, *, byte_table):
    """memory_analysis double for the per-bucket pre-flight: bytes looked up
    by (seq, batch_split)."""

    class _Analysis:
        def __init__(self, bytes_):
            self.argument_size_in_bytes = bytes_
            self.output_size_in_bytes = 0
            self.temp_size_in_bytes = 0
            self.alias_size_in_bytes = 0

    class _Compiled:
        def __init__(self, bytes_):
            self._b = bytes_

        def memory_analysis(self):
            return _Analysis(self._b)

    def compile_fn(trainer, seq, batch):
        compiles.append((seq, batch, trainer.batch_split))
        return _Compiled(byte_table[(seq, trainer.batch_split)])

    return compile_fn


def test_bucket_preflight_raises_split_and_rescales_loader(tmp_path):
    """Per-bucket HBM pre-flight: an over-limit bucket raises batch_split
    and RE-DERIVES every bucket's batch size before re-checking — mirroring
    QAEngine's per-bucket warmup pre-flight on the train side."""
    trainer, _ = _make_trainer(
        tmp_path, batch_split=1, length_buckets=[24, MAX_SEQ_LEN]
    )
    loader = trainer.train_dataloader
    sizes_before = dict(loader.batch_sizes)
    compiles = []
    # at split 1 the 48-bucket is over the 5k limit; at split 2 all fit
    byte_table = {
        (MAX_SEQ_LEN, 1): 9_000, (24, 1): 4_000,
        (MAX_SEQ_LEN, 2): 5_000, (24, 2): 2_500,
    }
    report = trainer.preflight_bucket_steps(
        compile_fn=_fake_bucket_compile_fn(compiles, byte_table=byte_table),
        limit_bytes=5_000,
    )
    assert trainer.batch_split == 2
    assert report["applied"] is True
    assert report["batch_split_before"] == 1 and report["batch_split"] == 2
    # checked largest seq first, re-planned once at the raised split
    assert [c[0] for c in compiles] == [MAX_SEQ_LEN, MAX_SEQ_LEN, 24]
    # the loader's bucket batches were re-derived for the new multiple
    assert loader.batch_multiple == 2 * 8  # batch_split * data axis
    assert loader.batch_sizes != sizes_before or all(
        v % 16 == 0 for v in loader.batch_sizes.values()
    )
    assert all(v % 16 == 0 for v in loader.batch_sizes.values())
    assert trainer._preflight_done


def test_bucket_preflight_noop_within_limit(tmp_path):
    trainer, _ = _make_trainer(
        tmp_path, batch_split=1, length_buckets=[24, MAX_SEQ_LEN]
    )
    compiles = []
    byte_table = {(MAX_SEQ_LEN, 1): 4_000, (24, 1): 2_000}
    report = trainer.preflight_bucket_steps(
        compile_fn=_fake_bucket_compile_fn(compiles, byte_table=byte_table),
        limit_bytes=5_000,
    )
    assert trainer.batch_split == 1 and report["applied"] is False
    assert len(compiles) == 2  # one compile per bucket, no re-plan
    assert len(report["buckets"]) == 2


def test_bucket_preflight_skips_off_bucket_or_no_limit(tmp_path):
    # not bucketed -> no-op even with a limit
    t_plain, _ = _make_trainer(tmp_path, batch_split=1)
    assert t_plain.preflight_bucket_steps(limit_bytes=1) is None
    # bucketed on CPU (no limit) -> stands down cleanly
    (tmp_path / "b").mkdir()
    t_b, _ = _make_trainer(
        tmp_path / "b", batch_split=1, length_buckets=[MAX_SEQ_LEN]
    )
    assert t_b.preflight_bucket_steps() is None
    assert t_b._preflight_done


def test_log_every_throttles_writer_updates(tmp_path):
    """The writer/tqdm cadence is throttled to every log_every steps (plus
    one final write), while meters and on_train_metrics see every step."""
    writes = []
    steps_seen = []

    class SpyWriter:
        def add_scalar(self, tag, value, global_step=None):
            writes.append((tag, global_step))

        def flush(self):
            pass

    trainer, _ = _make_trainer(
        tmp_path, train_len=64, log_every=3,
        on_train_metrics=lambda meters, step: steps_seen.append(step),
    )
    trainer.writer = SpyWriter()
    trainer.train()
    assert trainer.global_step == 4
    assert steps_seen == [0, 1, 2, 3]  # the tap still fires every step
    # writes at step 2 ((2+1) % 3 == 0) and the final write at step 3
    write_steps = sorted({s for _, s in writes})
    assert write_steps == [2, 3]


# ---------------------------------------------------------------------------
# ZeRO-1 sharded optimizer state (ISSUE 8)
# ---------------------------------------------------------------------------


def test_zero1_opt_state_bytes_reduction(tmp_path):
    """ISSUE-8 acceptance: on an N-device data mesh, zero1 reduces the
    MEASURED per-chip optimizer-state bytes by at least (N-1)/N of the
    replicated footprint of the leaves the plan shards — asserted against
    the same modeled arithmetic the HBM-planning probe reports."""
    import jax

    from ml_recipe_tpu.parallel.sharding import (
        opt_state_bytes_per_chip,
        zero1_state_bytes,
    )

    N = 8
    (tmp_path / "z").mkdir()
    z, _ = _make_trainer(tmp_path / "z", mesh_spec="data:8", dropout=0.0,
                         optimizer_sharding="zero1", zero_min_size=0)
    (tmp_path / "o").mkdir()
    o, _ = _make_trainer(tmp_path / "o", mesh_spec="data:8", dropout=0.0)

    measured_zero = opt_state_bytes_per_chip(z._split_ls()[0])
    measured_off = opt_state_bytes_per_chip(o._split_ls()[0])

    state_shapes = jax.eval_shape(o.optimizer.init, o.params)
    model = zero1_state_bytes(state_shapes, data_size=N, min_size=0)
    # measured == modeled, both directions (the probe's numbers are real)
    assert measured_off == model["replicated_bytes"]
    assert measured_zero == model["zero1_bytes"]
    # the acceptance inequality: savings >= (N-1)/N * sharded-leaf bytes,
    # up to the EXACT padding overhead (ceil shards of the padded leaves
    # hold slightly more than bytes/N) — which must itself be negligible
    nonsharded = model["replicated_bytes"] - model["sharded_bytes"]
    pad_overhead = (
        model["zero1_bytes"] - nonsharded - model["sharded_bytes"] / N
    )
    assert 0 <= pad_overhead < 0.01 * model["sharded_bytes"]
    assert (
        measured_off - measured_zero
        >= (N - 1) / N * model["sharded_bytes"] - pad_overhead - 1e-6
    )


def test_zero1_modeled_bytes_mocked_device_count():
    """The modeled arithmetic at an arbitrary (mocked) device count — no
    mesh, no devices: a v5e-64 plan computable on a laptop. Exact ceil
    arithmetic pinned on a padded leaf: (50,) f32 at N=8 pads to 56 and
    costs 7 floats per chip."""
    import jax

    from ml_recipe_tpu.parallel.sharding import zero1_state_bytes

    state = {
        "mu": {
            "kernel": jax.ShapeDtypeStruct((64, 32), jnp.float32),
            "bias": jax.ShapeDtypeStruct((50,), jnp.float32),
        },
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }
    out = zero1_state_bytes(state, data_size=8, min_size=0)
    assert out["replicated_bytes"] == 64 * 32 * 4 + 50 * 4 + 4
    # kernel shards evenly (64/8 rows), bias pads 50 -> 56 (7 per chip),
    # the scalar count stays replicated
    assert out["zero1_bytes"] == (64 * 32 // 8) * 4 + 7 * 4 + 4
    assert out["sharded_bytes"] == 64 * 32 * 4 + 50 * 4

    # a genuinely mocked pod width: N=64 on the same shapes
    wide = zero1_state_bytes(state, data_size=64, min_size=0)
    assert wide["zero1_bytes"] < out["zero1_bytes"]
    # min_size floor: everything below stays replicated
    floored = zero1_state_bytes(state, data_size=8, min_size=10 ** 9)
    assert floored["zero1_bytes"] == floored["replicated_bytes"]


def test_preflight_report_carries_opt_sharding_fields(tmp_path):
    """The HBM pre-flight must SEE the zero1 state: its report names the
    layout and the measured per-chip optimizer bytes, so a raised
    batch_split decision is auditable against the memory that actually
    exists."""
    from ml_recipe_tpu.parallel.sharding import opt_state_bytes_per_chip

    trainer, _ = _make_trainer(tmp_path, mesh_spec="data:8", batch_split=1,
                               optimizer_sharding="zero1", zero_min_size=0)
    report = trainer.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn([]), limit_bytes=10_000,
    )
    assert report["opt_sharding"] == "zero1"
    assert report["opt_state_bytes_per_chip"] == opt_state_bytes_per_chip(
        trainer._split_ls()[0]
    )
    (tmp_path / "off").mkdir()
    t_off, _ = _make_trainer(tmp_path / "off", batch_split=1)
    report_off = t_off.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn([]), limit_bytes=10_000,
    )
    assert report_off["opt_sharding"] == "off"
    assert (
        report_off["opt_state_bytes_per_chip"]
        > report["opt_state_bytes_per_chip"]
    )


def test_zero1_bad_mode_fails_at_build_time(tmp_path):
    with pytest.raises(ValueError, match="optimizer_sharding"):
        _make_trainer(tmp_path, optimizer_sharding="zero3")


class ZeroFinetuneTP(TP):
    finetune = True
    finetune_position = True
    finetune_class = True


def test_masks_share_one_path_walk_and_compose_with_zero1(tmp_path):
    """ISSUE-8 small fix: no_decay_mask and trainable_mask derive from the
    SAME path walk (param_path_mask), so they agree structurally on every
    leaf — including leaves neither existed for when the masks were two
    independent walks — and a frozen-encoder mask composes with zero1
    sharded state: training updates only the fine-tuned heads, bit-exact
    freezing for the rest."""
    import jax

    from ml_recipe_tpu.train.optim import (
        no_decay_mask,
        param_path_mask,
        trainable_mask,
    )

    trainer, _ = _make_trainer(
        tmp_path, mesh_spec="data:8", dropout=0.0, tp_cls=ZeroFinetuneTP,
        optimizer_sharding="zero1", zero_min_size=0,
    )
    decay = no_decay_mask(trainer.params)
    tmask = trainable_mask(trainer.params, ZeroFinetuneTP())
    # one walk, one structure: a new leaf cannot land in one mask but not
    # the other
    assert jax.tree_util.tree_structure(decay) == jax.tree_util.tree_structure(
        tmask
    )
    # the shared walk normalizes paths identically for both predicates
    probe = {"new_module": {"bias": np.zeros(4), "kernel": np.zeros((4, 4))}}
    assert param_path_mask(probe, lambda names: names[-1] == "bias") == {
        "new_module": {"bias": True, "kernel": False}
    }

    before = _param_snapshot(trainer.params)
    trainer.train()
    after = _param_snapshot(
        jax.tree_util.tree_map(lambda x: np.asarray(x), trainer.params)
    )
    flat_before = jax.tree_util.tree_flatten_with_path(before)[0]
    flat_after = jax.tree_util.tree_leaves(after)
    flat_mask = jax.tree_util.tree_leaves(tmask)
    changed_any = False
    for (path, x), y, trainable in zip(flat_before, flat_after, flat_mask):
        if trainable:
            changed_any = changed_any or not np.array_equal(x, y)
        else:
            np.testing.assert_array_equal(
                x, y, err_msg=f"frozen leaf {path} changed under zero1"
            )
    assert changed_any, "no fine-tuned leaf moved"


# ---------------------------------------------------------------------------
# Stage-local param/optimizer storage + 1F1B schedule (ISSUE 19)
# ---------------------------------------------------------------------------


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _stage_probe_params():
    """A QA-shaped ShapeDtypeStruct tree with power-of-two trunk dims —
    the modeled-bytes tests need the real key layout (stage scope is
    path-driven) but no devices."""
    def layer():
        return {"kernel": _sds(64, 64), "bias": _sds(64)}

    return {
        "transformer": {
            "embeddings": {"word_embeddings": _sds(128, 64)},
            "layer_0": layer(), "layer_1": layer(),
            "layer_2": layer(), "layer_3": layer(),
            "pooler": {"kernel": _sds(64, 64)},
        },
        "classifier": {"kernel": _sds(64, 5)},
    }


def test_stage_param_bytes_mocked_pipe_counts():
    """ISSUE-19 acceptance (modeled side, mocked stage counts K=2/4 — no
    mesh, no devices): stage-local storage puts per-chip param bytes at
    trunk/K + heads, i.e. within (1/K + eps) of the replicated footprint
    where eps is exactly the replicated pooler/head fraction."""
    from ml_recipe_tpu.parallel.pipeline import stage_param_bytes

    params = _stage_probe_params()
    trunk = (128 * 64 + 4 * (64 * 64 + 64)) * 4
    heads = (64 * 64 + 64 * 5) * 4
    for K in (2, 4):
        out = stage_param_bytes(params, pipe_size=K)
        assert out["pipe_size"] == K
        assert out["replicated_bytes"] == trunk + heads
        # every trunk dim divides K (powers of two): exact 1/K, no padding
        assert out["per_chip_bytes"] == trunk // K + heads
        eps = heads / (trunk + heads)
        assert (
            out["per_chip_bytes"]
            <= (1 / K + eps) * out["replicated_bytes"] + 1e-6
        )
        # ownership view conserves every byte; embeddings live with rank 0,
        # pooler/heads with the last stage
        per_stage = out["per_stage_bytes"]
        assert set(per_stage) == set(range(K))
        assert sum(per_stage.values()) == trunk + heads
        assert per_stage[0] >= 128 * 64 * 4
        assert per_stage[K - 1] >= heads


def test_zero1_under_pipe_modeled_bytes_compose():
    """zero1_state_bytes at a mocked data:2 x pipe:2: stage-scope moment
    leaves divide by BOTH axes (pipe claims its dim first, the padded-leaf
    data plan runs on what remains), pooler/head moments by data alone. A
    1-d trunk bias whose only dim the pipe axis claims stays data-
    replicated — the stage-local leaf set has nothing left to shard."""
    from ml_recipe_tpu.parallel.sharding import zero1_state_bytes

    params = _stage_probe_params()
    state = {"mu": params, "nu": params}
    both = zero1_state_bytes(state, data_size=2, min_size=0, pipe_size=2)
    data_only = zero1_state_bytes(state, data_size=2, min_size=0)
    emb, kernel, bias = 128 * 64 * 4, 64 * 64 * 4, 64 * 4
    pooler, classifier = 64 * 64 * 4, 64 * 5 * 4
    per_moment_repl = emb + 4 * (kernel + bias) + pooler + classifier
    assert data_only["replicated_bytes"] == 2 * per_moment_repl
    assert data_only["zero1_bytes"] == 2 * (per_moment_repl // 2)
    per_moment_both = (
        emb // 4                 # pipe on rows, data on cols
        + 4 * (kernel // 4       # pipe + data on the two 64-dims
               + bias // 2)      # pipe claims the ONLY dim: no data shard
        + pooler // 2 + classifier // 2  # heads: data only
    )
    assert both["zero1_bytes"] == 2 * per_moment_both
    assert both["zero1_bytes"] < data_only["zero1_bytes"]


def test_zero1_under_pipe_repads_on_stage_local_extents(tmp_path):
    """ISSUE-19: the ZeRO-1 padded-leaf plan under pipe runs WITHIN each
    stage's leaf set — pipe claims a divisible stage-scope dim with no
    padding, then the data axis pads its own (remaining) dim exactly as it
    would without pipe."""
    from ml_recipe_tpu.parallel.sharding import zero1_plan

    mesh = build_mesh("data:2,pipe:2")
    tree = {
        "mu": {
            "transformer": {
                # both dims divide: pipe takes one, data the other, no pad
                "layer_0": {"kernel": _sds(16, 16),
                            # 17 divides neither axis: pipe skips it (no
                            # padding on the pipe dim, ever), data pads
                            # 17 -> 18
                            "odd": _sds(17)},
            },
            # head leaf: pipe never touches it, data pads 17 -> 18 the
            # same way it does without a pipe axis
            "classifier": {"odd": _sds(17)},
        }
    }
    zplan = zero1_plan(tree, mesh, min_size=0, stage_pipe=True)
    kernel = zplan["mu"]["transformer"]["layer_0"]["kernel"]
    assert "pipe" in tuple(kernel.spec) and "data" in tuple(kernel.spec)
    assert kernel.padded == 16  # data dim present, unpadded
    trunk_odd = zplan["mu"]["transformer"]["layer_0"]["odd"]
    head_odd = zplan["mu"]["classifier"]["odd"]
    for leaf in (trunk_odd, head_odd):
        assert leaf.axis == 0 and leaf.padded == 18
        assert "pipe" not in (leaf.spec[0] or ())
    # and the no-pipe plan pads the head leaf identically: stage-local
    # re-padding changed nothing outside the stage scope
    flat = zero1_plan(tree, mesh, min_size=0, stage_pipe=False)
    assert flat["mu"]["classifier"]["odd"].padded == 18


def test_pipe_stage_preflight_byte_ratio(tmp_path):
    """ISSUE-19 acceptance (measured side): at data:2,pipe:2 the pre-flight
    report's param_bytes and opt_state_bytes_per_chip under stage-local
    storage land at <= (1/K + eps) of the replicated run's, eps being the
    replicated pooler/head share."""
    from ml_recipe_tpu.parallel.pipeline import stage_param_bytes

    s, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", batch_split=2,
                         optimizer_sharding="zero1", zero_min_size=0)
    (tmp_path / "r").mkdir()
    r, _ = _make_trainer(tmp_path / "r", mesh_spec="data:2,pipe:2",
                         batch_split=2, optimizer_sharding="zero1",
                         zero_min_size=0, pipe_param_sharding="replicated")
    assert s._stage_param_specs is not None and r._stage_param_specs is None
    rep_s = s.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn([]), limit_bytes=10_000)
    rep_r = r.preflight_train_step(
        None, None, compile_fn=_fake_compile_fn([]), limit_bytes=10_000)
    model = stage_param_bytes(r.params, pipe_size=2)
    K = 2
    # per_chip = trunk/K + heads  =>  trunk = (replicated - per_chip)*K/(K-1)
    trunk = (model["replicated_bytes"] - model["per_chip_bytes"]) * K // (K - 1)
    eps = (model["replicated_bytes"] - trunk) / model["replicated_bytes"]
    assert rep_r["param_bytes"] == model["replicated_bytes"]
    assert rep_s["param_bytes"] == model["per_chip_bytes"]
    assert (
        rep_s["param_bytes"]
        <= (1 / K + eps) * rep_r["param_bytes"] + 1e-6
    )
    # optimizer state: ZeRO-1 over data WITHIN the stage's leaf set — the
    # stage run's per-chip moments also drop to ~1/K of the replicated
    # run's (both already divide by data)
    assert (
        rep_s["opt_state_bytes_per_chip"]
        <= (1 / K + eps) * rep_r["opt_state_bytes_per_chip"] + 1e-6
    )
    # both reports name the layout they measured
    assert rep_s["pipe_param_layout"] == "stage"
    assert rep_r["pipe_param_layout"] == "replicated"


def test_pipe_1f1b_compiled_peak_below_gpipe(tmp_path):
    """ISSUE-19 acceptance (CPU smoke): at m=4 microbatches over K=2
    stages, the compiled 1F1B program's projected peak bytes
    (memory_analysis: args + outputs + temps - aliased) land strictly
    below gpipe's — the in-flight window (min(m, 2K-1) = 3 resident
    stage inputs) beats gpipe's all-m resident activations."""
    from ml_recipe_tpu.data.bucketing import synthetic_qa_batch
    from ml_recipe_tpu.utils.hbm import preflight_bytes

    host_in, host_lab = synthetic_qa_batch(16, MAX_SEQ_LEN)
    peaks = {}
    for sched in ("gpipe", "1f1b"):
        (tmp_path / sched).mkdir()
        tr, _ = _make_trainer(tmp_path / sched, mesh_spec="data:2,pipe:2",
                              batch_split=4, dropout=0.0,
                              pipe_schedule=sched)
        with tr.mesh:
            step = tr._build_train_step()
            di = tr._global_batch(tr._split_micro(host_in),
                                  leading_accum=True)
            dl = tr._global_batch(tr._split_micro(host_lab),
                                  leading_accum=True)
            compiled = step.lower(
                tr.params, tr.opt_state, di, dl, 0
            ).compile()
            peaks[sched] = preflight_bytes(compiled.memory_analysis())
    assert peaks["1f1b"] is not None and peaks["gpipe"] is not None
    assert peaks["1f1b"] < peaks["gpipe"], peaks

"""Data-parallel trajectory equivalence: mesh ``data:8`` vs a single device.

The framework claims DDP gradient-mean semantics (trainer.py header: GSPMD's
psum-mean over the data axis == DDP averaging, reference trainer/trainer.py:
197-204). Round-1 review: that claim was asserted, never tested. These tests
run the SAME seed and data order on an 8-way data mesh and on one device and
require the loss trajectory and final parameters to coincide within f32
reduction-reordering tolerance — with gradient accumulation and with ZeRO-1
optimizer-state sharding on the mesh side.

Dropout variants use ``threefry2x32`` (partitionable: bits depend only on
logical indices, so masks are mesh-invariant). The production default ``rbg``
is hardware-keyed and intentionally NOT mesh-invariant — DDP itself never
promised cross-topology dropout determinism (each reference GPU draws its own
torch masks).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_trainer import _make_trainer, _param_snapshot


def _run(trainer):
    """Train and return (per-step losses, final params)."""
    trainer._jit_train_step = trainer._build_train_step()
    inner = trainer._jit_train_step
    losses = []

    def recording_step(params, opt_state, inputs, labels, step):
        out = inner(params, opt_state, inputs, labels, step)
        losses.append(float(jax.device_get(out[2]["loss"])))
        return out

    trainer._jit_train_step = recording_step
    trainer.train()
    return losses, _param_snapshot(trainer.params)


def _assert_same_trajectory(a, b, *, rtol=2e-5, atol=2e-6, params_atol=1e-5):
    losses_a, params_a = a
    losses_b, params_b = b
    assert len(losses_a) == len(losses_b) and len(losses_a) >= 4
    np.testing.assert_allclose(
        losses_a, losses_b, rtol=rtol, atol=atol,
        err_msg="per-step loss trajectories diverge across meshes",
    )
    flat_a = jax.tree_util.tree_leaves(params_a)
    flat_b = jax.tree_util.tree_leaves(params_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            x, y, rtol=1e-4, atol=params_atol,
            err_msg="final params diverge across meshes",
        )


def test_dp8_matches_single_device(tmp_path):
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                          n_epochs=2)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1",
                              dropout=0.0, n_epochs=2)
    _assert_same_trajectory(_run(dp), _run(single))


def test_dp8_matches_single_device_with_batch_split(tmp_path):
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                          n_epochs=2, batch_split=2)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1",
                              dropout=0.0, n_epochs=2, batch_split=2)
    _assert_same_trajectory(_run(dp), _run(single))


def test_dp8_zero_matches_single_device(tmp_path):
    """ZeRO-1 sharded optimizer on the mesh vs plain replicated single-device:
    sharding the moments must not change the math (legacy shard_optimizer
    boolean spelling — kept as the back-compat pin)."""
    dp, _ = _make_trainer(
        tmp_path, mesh_spec="data:8", dropout=0.0, n_epochs=2,
        batch_split=2, shard_optimizer=True, zero_min_size=0,
    )
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1",
                              dropout=0.0, n_epochs=2, batch_split=2)
    _assert_same_trajectory(_run(dp), _run(single))


def test_zero1_single_chip_bit_identical_to_off(tmp_path):
    """ISSUE-8 acceptance: ``--optimizer_sharding zero1`` on a 1-chip mesh
    must produce a trajectory BIT-identical to ``off`` — with one device
    there is nothing to shard, and zero1 must take the replicated code
    path exactly (no padding, no constraints, no layout drift)."""
    z, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.0,
                         n_epochs=2, batch_split=2,
                         optimizer_sharding="zero1")
    off, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.0,
                           n_epochs=2, batch_split=2,
                           optimizer_sharding="off")
    assert z.opt_sharding_mode == "zero1" and not z.zero_enabled()
    losses_z, params_z = _run(z)
    losses_o, params_o = _run(off)
    assert len(losses_z) == len(losses_o) >= 4
    assert losses_z == losses_o, "1-chip zero1 trajectory not bit-identical"
    for x, y in zip(
        jax.tree_util.tree_leaves(params_z), jax.tree_util.tree_leaves(params_o)
    ):
        np.testing.assert_array_equal(
            x, y, err_msg="1-chip zero1 final params not bit-identical"
        )


def test_zero1_2way_matches_replicated(tmp_path):
    """ISSUE-8 acceptance (2-way): zero1 over data:2 vs the replicated
    layout on the same mesh — identical math up to deterministic-reduction
    reordering. data:2 exercises the padding-free divisible dims; the
    8-way variant below exercises the padded ones (e.g. the 5-label
    classifier bias padded 5 -> 8)."""
    z, _ = _make_trainer(tmp_path, mesh_spec="data:2", dropout=0.0,
                         n_epochs=2, batch_split=2,
                         optimizer_sharding="zero1", zero_min_size=0)
    off, _ = _make_trainer(tmp_path, mesh_spec="data:2", dropout=0.0,
                           n_epochs=2, batch_split=2)
    _assert_same_trajectory(_run(z), _run(off))


def test_zero1_8way_matches_replicated(tmp_path):
    """ISSUE-8 acceptance (wide way): zero1 over data:8 vs replicated on
    the same mesh, zero_min_size=0 so every leaf shards — including the
    padding-aware ones whose dims do not divide by 8."""
    z, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                         n_epochs=2, batch_split=2,
                         optimizer_sharding="zero1", zero_min_size=0)
    off, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                           n_epochs=2, batch_split=2)
    _assert_same_trajectory(_run(z), _run(off))


def test_dp8_matches_single_device_with_threefry_dropout(tmp_path):
    """With the partitionable threefry PRNG, even the dropout masks are a
    function of logical index only — the full stochastic trajectory must be
    mesh-invariant."""
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.1,
                          n_epochs=2, prng_impl="threefry2x32")
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1",
                              dropout=0.1, n_epochs=2,
                              prng_impl="threefry2x32")
    _assert_same_trajectory(_run(dp), _run(single))


def test_dp_tp_mesh_matches_single_device(tmp_path):
    """dp x tp (data:4, model:2): tensor-parallel sharding of the encoder
    must not change the math either — same trajectory as one device."""
    dptp, _ = _make_trainer(tmp_path, mesh_spec="data:4,model:2",
                            dropout=0.0, n_epochs=2)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1",
                              dropout=0.0, n_epochs=2)
    # params_atol: TP psum reduction reordering shifts near-zero leaves by
    # ~1e-5 absolute while the loss trajectory stays tight
    _assert_same_trajectory(_run(dptp), _run(single), params_atol=5e-5)


def test_sp_ring_mesh_matches_single_device(tmp_path):
    """data x seq (data:2, seq:4) with RING attention vs one device: the
    sequence-parallel training trajectory must coincide with the
    single-device one (VERDICT r3 weak #6: the suite had op/model-level ring
    equivalence but no training-trajectory proof). Deterministic variant."""
    sp, _ = _make_trainer(tmp_path, mesh_spec="data:2,seq:4", dropout=0.0,
                          n_epochs=2, attention_impl="ring")
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.0,
                              n_epochs=2)
    _assert_same_trajectory(_run(sp), _run(single), params_atol=5e-5)


def test_bucketed_path_bit_matches_unbucketed_on_equal_lengths(tmp_path):
    """ISSUE 4 acceptance: on equal-length data (every DummyDataset item is
    exactly MAX_SEQ_LEN tokens) a single-bucket grid reproduces the
    unbucketed path's batches EXACTLY — same epoch ordering, same shapes,
    same compiled program — so the loss trajectory and final params must be
    bit-identical, not merely close."""
    from test_trainer import MAX_SEQ_LEN

    bucketed, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                                n_epochs=2, length_buckets=[MAX_SEQ_LEN])
    plain, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                             n_epochs=2)
    losses_b, params_b = _run(bucketed)
    losses_p, params_p = _run(plain)
    assert len(losses_b) == len(losses_p) >= 4
    assert losses_b == losses_p, "bucketed loss trajectory not bit-identical"
    for x, y in zip(
        jax.tree_util.tree_leaves(params_b), jax.tree_util.tree_leaves(params_p)
    ):
        np.testing.assert_array_equal(
            x, y, err_msg="bucketed final params not bit-identical"
        )


def test_sp_ring_seq_shard_invariant_with_dropout(tmp_path):
    """Stochastic variant: ring's in-flight dropout streams are keyed by
    GLOBAL row/col indices (seq-shard-count invariant, op-level pinned in
    test_ring_attention) and hidden dropout uses threefry — so the training
    trajectory over data:2,seq:4 must match data:2,seq:2, dropout LIVE in
    both. The DATA axis must stay fixed: ring deliberately folds the dp
    coordinate into the seed (dp decorrelation, ring_attention._dropout_ids),
    so masks are seq-invariant but intentionally NOT dp-layout-invariant —
    the reference's DDP likewise drew independent torch masks per GPU."""
    sp, _ = _make_trainer(tmp_path, mesh_spec="data:2,seq:4", dropout=0.1,
                          n_epochs=2, attention_impl="ring",
                          prng_impl="threefry2x32")
    small, _ = _make_trainer(tmp_path, mesh_spec="data:2,seq:2", dropout=0.1,
                             n_epochs=2, attention_impl="ring",
                             prng_impl="threefry2x32")
    _assert_same_trajectory(_run(sp), _run(small), params_atol=5e-5)


def test_sp_composed_stream_matches_dp_at_512(tmp_path):
    """ISSUE 20 satellite: at seq 512 the ``data:2,seq:2`` mesh runs the
    COMPOSED streaming-ring inner (L_loc=256 has a legal streaming
    geometry, interpret-mode kernels on CPU) — its training trajectory
    must match a pure data-parallel ``data:4`` run of the same global
    batch. Dropout stays off: ring deliberately folds the dp coordinate
    into its dropout seed, so stochastic trajectories are only comparable
    at a FIXED data-axis size (see test_sp_ring_seq_shard_invariant)."""
    from ml_recipe_tpu.ops.ring_attention import ring_stream_geometry

    # the premise of the pin: 512/2 has a streaming geometry on this path
    assert ring_stream_geometry(256, 2, 8, jnp.float32, 0.0,
                                interpret=True) is not None

    sp, _ = _make_trainer(tmp_path, mesh_spec="data:2,seq:2", dropout=0.0,
                          n_epochs=2, attention_impl="ring",
                          max_seq_len=512)
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.0,
                          n_epochs=2, max_seq_len=512)
    _assert_same_trajectory(_run(sp), _run(dp), rtol=5e-5, atol=5e-6,
                            params_atol=5e-5)


def test_pack_splitting_off_bit_matches_head(tmp_path):
    """ISSUE 11 acceptance: ``--pack_splitting off`` (the default) is the
    pre-splitting packed code path bit-exactly — a packed trainer with the
    flag explicitly off must produce the same trajectory, bit for bit, as
    one that never saw the flag (guards against splitting-code leakage
    into the non-splitting packer: placement walk, collate planes, stats
    and plan must all be untouched)."""
    from test_packing import _packed_trainer

    off_dir = tmp_path / "off"
    off_dir.mkdir()
    default_dir = tmp_path / "default"
    default_dir.mkdir()
    off = _packed_trainer(off_dir, pack_splitting="off", pack_min_fragment=4)
    default = _packed_trainer(default_dir)
    losses_o, params_o = _run(off)
    losses_d, params_d = _run(default)
    assert len(losses_o) == len(losses_d) >= 1
    assert losses_o == losses_d, (
        "pack_splitting-off loss trajectory not bit-identical"
    )
    for x, y in zip(
        jax.tree_util.tree_leaves(params_o), jax.tree_util.tree_leaves(params_d)
    ):
        np.testing.assert_array_equal(
            x, y, err_msg="pack_splitting-off final params not bit-identical"
        )
    assert off._planned_steps_per_epoch == default._planned_steps_per_epoch


def test_sequence_packing_off_bit_matches_head(tmp_path):
    """ISSUE 5 acceptance: ``--sequence_packing off`` (the default) is the
    pre-packing code path bit-exactly — a trainer constructed with the flag
    explicitly off must produce the same trajectory, bit for bit, as one
    that never saw the flag (guards against accidental default-on or
    packed-code leakage into the plain path)."""
    off, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                           n_epochs=2, sequence_packing=False)
    default, _ = _make_trainer(tmp_path, mesh_spec="data:8", dropout=0.0,
                               n_epochs=2)
    losses_o, params_o = _run(off)
    losses_d, params_d = _run(default)
    assert len(losses_o) == len(losses_d) >= 4
    assert losses_o == losses_d, "packing-off loss trajectory not bit-identical"
    for x, y in zip(
        jax.tree_util.tree_leaves(params_o), jax.tree_util.tree_leaves(params_d)
    ):
        np.testing.assert_array_equal(
            x, y, err_msg="packing-off final params not bit-identical"
        )


def _run_aot(trainer):
    """``_run`` for store-enabled trainers: record losses AROUND the
    AOT-dispatched executable instead of swapping ``_jit_train_step`` for
    a plain function (which cannot ``.lower()`` and would make the
    trainer bypass the store entirely — exactly what these pins must not
    do)."""
    losses = []
    real = trainer._aot_train_step_program

    def recording_program(dev_inputs, dev_labels):
        program = real(dev_inputs, dev_labels)

        def rec(params, opt_state, inputs, labels, step):
            out = program(params, opt_state, inputs, labels, step)
            losses.append(float(jax.device_get(out[2]["loss"])))
            return out

        return rec

    trainer._aot_train_step_program = recording_program
    trainer.train()
    return losses, _param_snapshot(trainer.params)


def test_aot_cache_off_bit_matches_enabled_store(tmp_path):
    """ISSUE-17 acceptance: ``--aot_cache off`` (the store disabled — the
    HEAD jit-dispatch path verbatim) and BOTH store outcomes — a cold run
    against an empty store (miss: store-owned compile) and a warm restart
    (hit: the deserialized executable, zero XLA compiles) — must produce
    bit-identical loss trajectories and final params."""
    from ml_recipe_tpu.ops import aot

    store_dir = tmp_path / "store"

    def fresh(sub):
        d = tmp_path / sub
        d.mkdir()
        t, _ = _make_trainer(d, mesh_spec="data:8", dropout=0.0, n_epochs=2)
        return t

    try:
        aot.reset().enabled = False  # --aot_cache off
        off = _run(fresh("off"))
        assert aot.get().hits == 0 and aot.get().misses == 0

        aot.reset()
        aot.configure(enabled=True, cache_dir=store_dir)
        cold = _run_aot(fresh("cold"))
        store = aot.get()
        assert store.misses >= 1 and store.hits == 0, (
            "empty store must cold-compile (and persist) every program"
        )

        aot.reset()
        aot.configure(enabled=True, cache_dir=store_dir)
        warm = _run_aot(fresh("warm"))
        store = aot.get()
        assert store.misses == 0 and store.hits >= 1, (
            "warm restart must deserialize every program: zero XLA compiles"
        )
    finally:
        aot.reset()

    for name, (losses, params) in (("cold", cold), ("warm", warm)):
        losses_o, params_o = off
        assert len(losses) == len(losses_o) >= 4
        assert losses == losses_o, (
            f"{name}-store loss trajectory not bit-identical to --aot_cache off"
        )
        for x, y in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(params_o),
        ):
            np.testing.assert_array_equal(
                x, y,
                err_msg=f"{name}-store final params not bit-identical "
                        "to --aot_cache off",
            )


def test_pipe2_matches_data4(tmp_path):
    """ISSUE-15 acceptance: ``--mesh data:2,pipe:2`` trains the SAME
    trajectory as ``data:4`` at identical data order — the GPipe schedule
    (shard_map stages + ppermute hand-off, parallel/pipeline.py)
    accumulates gradients across micro-batches exactly as the sequential
    scan, so only GSPMD reduction reordering separates the two runs (the
    zero1-vs-replicated tolerance)."""
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.0,
                          n_epochs=2, batch_split=4)
    pipe, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2",
                            dropout=0.0, n_epochs=2, batch_split=4)
    assert pipe.pipe_stages == 2
    _assert_same_trajectory(_run(dp), _run(pipe))


def test_pipe2_zero1_matches_data4(tmp_path):
    """ZeRO-1 runs under a pipe-bearing mesh, deriving its layouts from the
    one ParallelPlan, and stays within the zero1-vs-replicated tolerance of
    the plain data:4 run."""
    ref, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.0,
                           n_epochs=2, batch_split=4)
    ref_run = _run(ref)
    z, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.0,
                         n_epochs=2, batch_split=4,
                         optimizer_sharding="zero1", zero_min_size=0)
    _assert_same_trajectory(ref_run, _run(z))
    assert z.zero_enabled()


def test_pipe_stage_sharded_matches_replicated(tmp_path):
    """ISSUE-19: stage-local param/optimizer storage (each pipe rank
    holds only its own stage's trunk slice; the island all-gathers per
    step) trains the SAME trajectory as the PR-15 replicated-stage
    layout — the layout changes WHERE bytes live, never the math."""
    rep, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2",
                           dropout=0.0, n_epochs=2, batch_split=2,
                           pipe_param_sharding="replicated")
    assert rep._stage_param_specs is None
    st, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2",
                          dropout=0.0, n_epochs=2, batch_split=2)
    assert st._stage_param_specs is not None
    _assert_same_trajectory(_run(rep), _run(st))


def test_pipe2_1f1b_matches_gpipe_m124(tmp_path):
    """ISSUE-19 acceptance: ``--pipe_schedule 1f1b`` accumulates
    gradients exactly as the GPipe tick scan at identical data order —
    trajectory parity at m = 1, 2 and 4 micro-batches within the PR-15
    pipeline tolerance. (m=1 exercises the degenerate fused
    fwd+bwd-per-tick program; m=4 > 2K-1 exercises the in-flight ring
    buffer wrap.)"""
    for m in (1, 2, 4):
        g, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2",
                             dropout=0.0, n_epochs=2, batch_split=m)
        f, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2",
                             dropout=0.0, n_epochs=2, batch_split=m,
                             pipe_schedule="1f1b")
        assert f.pipe_schedule == "1f1b"
        _assert_same_trajectory(_run(g), _run(f))


def test_pipe2_1f1b_zero1_matches_gpipe(tmp_path):
    """1F1B composes with ZeRO-1 over ``data`` on the stage-local leaf
    sets: the stage-sharded grads re-pad onto the pipe x data plan and
    the trajectory stays pinned to the gpipe run."""
    g, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.0,
                         n_epochs=2, batch_split=4,
                         optimizer_sharding="zero1", zero_min_size=0)
    f, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.0,
                         n_epochs=2, batch_split=4,
                         optimizer_sharding="zero1", zero_min_size=0,
                         pipe_schedule="1f1b")
    _assert_same_trajectory(_run(g), _run(f))


def test_pipe2_1f1b_live_dropout_trains_and_is_deterministic(tmp_path):
    """Regression: 1F1B with dropout LIVE under the default ``rbg`` PRNG.

    The island's micro index is pipe-rank-varying (f = t - k), so its
    dropout keys are varying — rbg's rng_bit_generator would make XLA
    broadcast one rank's key via u64 all-reduces placed inside the
    stage-divergent switch branches, where stage 0 and stage 1 wait on
    different channels: a runtime DEADLOCK the dropout=0.0 parity tests
    above never exercise (pipeline.py re-seeds threefry instead). Pin
    that the run completes with finite falling losses and that two
    identical runs stay bit-deterministic."""
    a, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.1,
                         n_epochs=2, batch_split=4, pipe_schedule="1f1b")
    losses_a, params_a = _run(a)
    assert len(losses_a) >= 4 and all(np.isfinite(losses_a))
    assert losses_a[-1] < losses_a[0]
    b, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.1,
                         n_epochs=2, batch_split=4, pipe_schedule="1f1b")
    _assert_same_trajectory((losses_a, params_a), _run(b),
                            rtol=0, atol=0, params_atol=0)


def test_pipe2_model2_matches_model2_alone(tmp_path):
    """ISSUE-19 acceptance: ``pipe:2,model:2`` constructs and trains
    (the PR-15 NotImplementedError is gone) — stage specs keep their TP
    dims and the trajectory matches the non-pipe TP mesh within the TP
    tolerance. Both schedules pinned."""
    tp, _ = _make_trainer(tmp_path, mesh_spec="model:2", dropout=0.0,
                          n_epochs=2, batch_split=2)
    tp_run = _run(tp)
    for sched in ("gpipe", "1f1b"):
        pm, _ = _make_trainer(tmp_path, mesh_spec="pipe:2,model:2",
                              dropout=0.0, n_epochs=2, batch_split=2,
                              pipe_schedule=sched)
        assert pm.pipe_stages == 2 and pm.plan.model_size == 2
        # Looser than the PR-15 pin: the pipe island computes gathered
        # full-width matmuls (grad psum canceled by _bwd_scale) while the
        # reference runs TP-sharded matmul+psum — a different reduction
        # order whose ~1e-7 rounding Adam amplifies to ~2e-4 on the loss
        # and ~6e-4 absolute on near-zero params within 4 steps. A real
        # math bug (wrong scale, missing psum) diverges at O(1).
        _assert_same_trajectory(tp_run, _run(pm), rtol=5e-4, atol=1e-4,
                                params_atol=2e-3)


# ---------------------------------------------------------------------------
# ISSUE 25: one gradient exchange a step on data-only meshes (the data island)
# ---------------------------------------------------------------------------

def _assert_exchanges(trainer, per_step):
    """After ``_run``: which step body the trainer built."""
    assert trainer.grad_exchanges_per_step == per_step


@pytest.mark.parametrize("mesh_spec,batch_split,batch", [
    ("data:4", 2, 16), ("data:4", 4, 16), ("data:8", 2, 16),
    ("data:8", 4, 32),
])
def test_exchange_once_matches_single_device(tmp_path, mesh_spec,
                                             batch_split, batch):
    """The micro-batch loop as a data island (each chip accumulates its own
    unreduced gradient sum, one f32 reduction follows the loop) trains the
    trajectory of one device, within the file's tolerances."""
    kw = dict(dropout=0.0, n_epochs=2, batch_split=batch_split,
              train_batch_size=batch, train_len=2 * batch)
    dp, _ = _make_trainer(tmp_path, mesh_spec=mesh_spec, **kw)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", **kw)
    dp_run, single_run = _run(dp), _run(single)
    _assert_exchanges(dp, 1)
    _assert_exchanges(single, 0)
    _assert_same_trajectory(dp_run, single_run)


def test_exchange_once_with_dynamic_loss_scale(tmp_path):
    """apex-parity loss scaling: the scale enters the island replicated, the
    loss is scaled inside each chip's grad, and the one reduction carries
    scaled f32 sums that ``finish_step`` unscales as ever."""
    from test_trainer import TP

    tp_cls = type("TPls", (TP,), {"apex_loss_scale": "dynamic"})
    kw = dict(dropout=0.0, n_epochs=2, batch_split=2, tp_cls=tp_cls)
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", **kw)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", **kw)
    dp_run = _run(dp)
    _assert_exchanges(dp, 1)
    assert dp._use_loss_scale
    _assert_same_trajectory(dp_run, _run(single))


def test_one_micro_batch_keeps_the_gspmd_body(tmp_path):
    """``batch_split`` 1 has one exchange a step already: nothing to
    restructure, the plain GSPMD body stays."""
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.0)
    dp._jit_train_step = dp._build_train_step()
    _assert_exchanges(dp, 1)
    assert "shard_map" not in str(jax.make_jaxpr(
        lambda *a: dp._jit_train_step(*a))(*_step_args(dp)))


@pytest.mark.parametrize("mesh_spec", ["data:4", "data:8"])
def test_exchange_once_zero1_matches_single_device(tmp_path, mesh_spec):
    """ZeRO-1 receives the island's accumulated gradient in the layout it
    always did."""
    z, _ = _make_trainer(
        tmp_path, mesh_spec=mesh_spec, dropout=0.0, n_epochs=2,
        batch_split=2, optimizer_sharding="zero1", zero_min_size=0)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.0,
                              n_epochs=2, batch_split=2)
    z_run = _run(z)
    _assert_exchanges(z, 1)
    assert z.zero_enabled()
    _assert_same_trajectory(z_run, _run(single))


def _ignore_rows_unevenly(trainer, cls_ignore, n_ignored=5):
    """Rewrite every train batch's labels so that the first ``n_ignored``
    rows carry ignored spans (-1) and the next two the class head's ignore
    index (None: that head ignores nothing): with a few rows a chip a
    micro-batch, the chips of the first micro-batch hold 0, 0, 1, 2, ...
    valid span rows. A per-chip normaliser then weights the chips' rows
    wrongly."""
    loader = trainer.train_dataloader
    collate = loader.collate_fun

    def uneven(items):
        inputs, labels = collate(items)
        labels = {k: np.array(v) for k, v in labels.items()}
        labels["start_class"][:n_ignored] = -1
        labels["end_class"][:n_ignored + 1] = -1
        if cls_ignore is not None:
            labels["cls"][n_ignored:n_ignored + 2] = cls_ignore
        return [inputs, labels]

    loader.collate_fun = uneven


@pytest.mark.parametrize("mesh_spec", ["data:4", "data:8"])
@pytest.mark.parametrize("loss_kind", ["ce_weighted", "smooth", "focal"])
def test_exchange_once_uneven_ignored_rows(tmp_path, mesh_spec, loss_kind):
    """Every loss term divides by the GLOBAL micro-batch's normaliser (valid
    span rows, class weights of the valid class rows, the row count): with
    ignored rows falling unevenly on the chips, and class weights, the
    chips' shares still add up to one device's gradient and values."""
    from test_trainer import TP

    tp_cls = type("TPk", (TP,), {
        "loss": "ce" if loss_kind == "ce_weighted" else loss_kind,
        "smooth_alpha": 0.1})
    weights = None
    if loss_kind == "ce_weighted":
        weights = {"label_weights": np.array([0.2, 1.0, 3.0, 0.5, 2.0],
                                             np.float32)}
    kw = dict(dropout=0.0, n_epochs=2, batch_split=2, tp_cls=tp_cls,
              train_weights=weights)
    dp, _ = _make_trainer(tmp_path, mesh_spec=mesh_spec, **kw)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", **kw)
    # the class head's ignore index: CE -100, focal -1 (the reference's
    # defaults), KLDiv-smoothing none
    cls_ignore = {"ce_weighted": -100, "focal": -1, "smooth": None}[loss_kind]
    for t in (dp, single):
        _ignore_rows_unevenly(t, cls_ignore)
    dp_run = _run(dp)
    _assert_exchanges(dp, 1)
    _assert_same_trajectory(dp_run, _run(single))


@pytest.mark.parametrize("kind", ["packed", "bucketed"])
def test_exchange_once_packed_and_bucketed_on_data8(tmp_path, kind):
    """Sequence-packed rows (per-segment labels: the real segments fall
    unevenly on the chips by construction) and length-bucketed batches run
    through the island on ``data:8`` like plain ones."""
    from test_packing import _packed_trainer

    extra = (dict(sequence_packing=True) if kind == "packed" else
             dict(sequence_packing=False, length_buckets=[24, 48]))

    def make(sub, mesh_spec):
        d = tmp_path / sub
        d.mkdir()
        return _packed_trainer(d, mesh_spec=mesh_spec, dropout=0.0,
                               train_batch_size=16, batch_split=2,
                               n_epochs=4, **extra)

    dp, single = make("dp", "data:8"), make("one", "data:1")
    dp_run = _run(dp)
    _assert_exchanges(dp, 1)
    _assert_same_trajectory(dp_run, _run(single))


def _step_args(trainer):
    """One placed batch and the step's other arguments."""
    inputs, labels = next(iter(trainer.train_dataloader))
    place = lambda t: trainer._global_batch(  # noqa: E731
        trainer._split_micro(t), leading_accum=True)
    return (trainer.params, trainer.opt_state, place(inputs), place(labels),
            0)


def _elements(types_text):
    """Elements of every array type (``f32[16,32]``) in a piece of HLO."""
    import re

    return sum(
        int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        for dims in re.findall(r"\w+\[([\d,]*)\]", types_text))


def _while_body_collectives(hlo_text):
    """``(name, elements)`` of every collective inside a while body (or a
    computation one calls) of an optimized HLO text."""
    import re

    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    ref = re.compile(
        r"(?:body|condition|to_apply|calls|branch_computations)="
        r"\{?%?([\w.\-]+)")
    inside = {m.group(1) for lines in comps.values() for l in lines
              for m in re.finditer(r"body=%?([\w.\-]+)", l)}
    todo = list(inside)
    while todo:
        for l in comps.get(todo.pop(), []):
            for m in ref.finditer(l):
                if m.group(1) in comps and m.group(1) not in inside:
                    inside.add(m.group(1))
                    todo.append(m.group(1))
    found = []
    op = re.compile(r"=\s*(.*?)\s(all-reduce|all-gather|reduce-scatter|"
                    r"collective-permute|all-to-all)(?:-start)?\(")
    for name in inside:
        for l in comps[name]:
            m = op.search(l)
            if m:
                found.append((m.group(2), _elements(m.group(1))))
    return found, bool(inside)


@pytest.mark.parametrize("mesh_spec,batch_split", [("data:4", 4),
                                                   ("data:8", 2)])
def test_no_collective_inside_the_micro_batch_loop(tmp_path, mesh_spec,
                                                   batch_split):
    """Structural: the optimized HLO of the ``data:N``, ``batch_split > 1``
    step has no collective at all inside the while body (today's body had
    every weight gradient's all-reduce there), and the f32 gradient crosses
    the mesh after it."""
    dp, _ = _make_trainer(tmp_path, mesh_spec=mesh_spec, dropout=0.1,
                          batch_split=batch_split)
    step = dp._build_train_step()
    with dp.mesh:
        text = step.lower(*_step_args(dp)).compile().as_text()
    inside, has_loop = _while_body_collectives(text)
    assert has_loop, "no while loop in the step: the probe sees nothing"
    assert inside == [], inside
    # ... as a tree: `%all-reduce`s the trace readers know by name (a
    # `lax.psum` would run the same exchange as `%psum.N`), which XLA's
    # combiner may group, carrying every parameter's f32 gradient once (and
    # the handful of loss values) and no vector of the whole gradient
    import re

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(dp.params))
    carried = sum(
        _elements(m.group(1)) for m in re.finditer(
            r"%all-reduce[\w.\-]* = (.*?) all-reduce(?:-start)?\(", text))
    assert n_params <= carried <= n_params + 64, (carried, n_params)
    assert not re.search(rf"f32\[(1,)?{n_params}\]", text)


def test_gspmd_body_still_reduces_inside_the_loop(tmp_path):
    """The probe itself: on the body this PR left alone (threefry keeps the
    GSPMD body) it does find the gradient all-reduces inside the loop."""
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.1,
                          batch_split=4, prng_impl="threefry2x32")
    step = dp._build_train_step()
    _assert_exchanges(dp, 4)
    with dp.mesh:
        text = step.lower(*_step_args(dp)).compile().as_text()
    inside, has_loop = _while_body_collectives(text)
    assert has_loop and any(n > 1000 for _, n in inside), inside


# sha256 of the one-chip step's StableHLO text (no locations) for the tiny
# test model, batch_split 2, dropout 0.1. The one-chip program is
# `base-train-full512`'s: a change here is a new compile-cache key there (a
# cold `setup_s`) and has to be meant. Re-pinned on purpose at ISSUE 30: the
# micro-batch loop's carry became the parameters' tree (per tensor) where it
# was one flat vector.
ONE_CHIP_STEP_SHA256 = "3c7656b1bbab823f5111ae02061ff4defd192471fa3c4ddcc1f341e5ae0eb41d"


def test_one_chip_step_program_is_unchanged(tmp_path):
    import hashlib

    one, _ = _make_trainer(tmp_path, mesh_spec="data:1", dropout=0.1,
                           batch_split=2)
    step = one._build_train_step()
    _assert_exchanges(one, 0)
    text = step.lower(*_step_args(one)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_CHIP_STEP_SHA256


def test_threefry_keeps_the_gspmd_body_and_its_mesh_invariance(tmp_path):
    """The partitionable threefry generator's masks are a function of the
    logical index alone: such a run stays on the GSPMD body, so that the
    stochastic trajectory is still that of one device with
    ``batch_split > 1`` too (the island's per-chip draws would break it)."""
    kw = dict(dropout=0.1, n_epochs=2, batch_split=2,
              prng_impl="threefry2x32")
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:8", **kw)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", **kw)
    dp_run = _run(dp)
    _assert_exchanges(dp, 2)
    _assert_same_trajectory(dp_run, _run(single))


def test_island_dropout_is_deterministic(tmp_path):
    """Same ``(seed, mesh)`` twice: the same stochastic trajectory."""
    runs = []
    for _ in range(2):
        t, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.1,
                             n_epochs=2, batch_split=2)
        runs.append(_run(t))
        _assert_exchanges(t, 1)
    _assert_same_trajectory(*runs, rtol=0, atol=0, params_atol=0)


def test_island_chips_draw_different_hidden_masks(tmp_path):
    """Two chips given the SAME rows and the same micro-batch key must not
    drop the same hidden units. Seen from outside: every row of the batch
    is one row; only one chip's rows carry labels (the others are ignored
    by every head that can ignore). Were the masks equal, the loss would
    not depend on WHICH chip holds the labelled rows."""
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:2", dropout=0.3,
                          batch_split=2)
    step = dp._build_train_step()
    _assert_exchanges(dp, 1)
    params, opt_state, inputs, labels, _ = _step_args(dp)

    def same_rows(x):       # [G, B, ...]: every row is row (0, 0)
        x = np.asarray(x)
        return np.broadcast_to(x[:1, :1], x.shape).copy()

    inputs = jax.tree_util.tree_map(same_rows, inputs)
    labels = jax.tree_util.tree_map(same_rows, labels)
    rows = labels["cls"].shape[1]

    def loss_with_labels_on(chip):
        lab = {k: v.copy() for k, v in labels.items()}
        off = np.ones(rows, bool)
        off[chip * (rows // 2):(chip + 1) * (rows // 2)] = False
        for key, ignore in (("start_class", -1), ("end_class", -1),
                            ("cls", -100)):
            lab[key][:, off] = ignore
        place = lambda t: dp._global_batch(t, leading_accum=True)  # noqa: E731
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        with dp.mesh:
            out = step(copy(params), copy(opt_state), place(inputs),
                       place(lab), 0)
        return {k: float(v) for k, v in jax.device_get(out[2]).items()}

    a, b = loss_with_labels_on(0), loss_with_labels_on(1)
    for head in ("start_class", "end_class", "cls"):
        assert np.isfinite(a[head]) and a[head] != b[head], (head, a, b)


@pytest.mark.parametrize("attention_impl", ["pallas", "xla"])
def test_island_attention_dropout_is_that_of_one_device(tmp_path, monkeypatch,
                                                        attention_impl):
    """Trainer level: with hidden dropout off and attention dropout LIVE,
    the island's trajectory is one device's, under the default ``rbg``
    generator too (on the chip: ``chip_smoke.py --chips 4``'s
    ``attention_dropout_step``). Attention draws from the micro-batch key
    WITHOUT the chip's fold (flax name "attention_dropout"): the kernels
    (interpret mode here) key their masks by global row, and XLA attention
    takes the chip's rows of the whole micro-batch's draw."""
    import functools

    from ml_recipe_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        functools.partial(fa.flash_attention, interpret=True))
    kw = dict(dropout=0.1, n_epochs=2, batch_split=2, max_seq_len=128,
              attention_impl=attention_impl,
              cfg_overrides=dict(hidden_size=128, num_heads=2,
                                 intermediate_size=64,
                                 hidden_dropout_prob=0.0))
    dp, _ = _make_trainer(tmp_path, mesh_spec="data:4", **kw)
    single, _ = _make_trainer(tmp_path, mesh_spec="data:1", **kw)
    dp_run = _run(dp)
    _assert_exchanges(dp, 1)
    single_run = _run(single)
    assert dp_run[0] != _run_without_dropout_losses(tmp_path, kw), (
        "dropout was not live")
    _assert_same_trajectory(dp_run, single_run)


def _run_without_dropout_losses(tmp_path, kw):
    kw = dict(kw, dropout=0.0, mesh_spec="data:1")
    trainer, _ = _make_trainer(tmp_path, **kw)
    return _run(trainer)[0]

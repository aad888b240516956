"""ISSUE-15: the declarative ParallelPlan and the pipe axis.

Covers the plan as the single source of truth for every layout
(trainer opt-state shardings, batch placement, checkpoint manifests,
pre-flight topology records), the --mesh grammar hardening, the
stranded-device accounting, pipelined-forward parity against the
sequential model, and the GPipe schedule's measured bubble fraction
tracking the (K-1)/(K-1+m) model — the proof the overlap is real, not
sequential.
"""

import logging
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ml_recipe_tpu.config.parser import MESH_HELP, parse_mesh_spec
from ml_recipe_tpu.parallel import ParallelPlan, build_mesh, unused_device_count
from ml_recipe_tpu.parallel.pipeline import (
    apply_qa_heads,
    make_pipeline_encoder,
    measured_bubble_fractions,
    modeled_bubble_fraction,
    stage_layer_count,
    validate_pipeline_plan,
)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from test_trainer import _make_trainer  # noqa: E402


# -- --mesh grammar hardening -------------------------------------------------

def test_parse_mesh_spec_accepts_all_axes():
    assert parse_mesh_spec("data:2,seq:1,model:1,pipe:2") == {
        "data": 2, "seq": 1, "model": 1, "pipe": 2,
    }
    assert parse_mesh_spec(None) == {}
    assert parse_mesh_spec("data=4") == {"data": 4}


@pytest.mark.parametrize("bad,match", [
    ("data:2,data:4", "duplicate axis"),
    ("data:0", "size must be >= 1"),
    ("pipe:-1", "size must be >= 1"),
    ("data:x", "non-integer size"),
    ("data", "malformed entry"),
    ("data:", "malformed entry"),
])
def test_parse_mesh_spec_rejects_bad_specs(bad, match):
    with pytest.raises(ValueError, match=match):
        parse_mesh_spec(bad)


def test_mesh_help_is_one_shared_constant():
    """The two --mesh registrations (trainer and predictor/serve parsers)
    carry the SAME help text — the divergent hand-maintained copies this
    PR unified — and it documents every axis including pipe."""
    from ml_recipe_tpu.config.parser import (
        get_serve_parser,
        get_trainer_parser,
    )

    helps = []
    for factory in (get_trainer_parser, get_serve_parser):
        for action in factory()._actions:
            if "--mesh" in action.option_strings:
                helps.append(action.help)
    assert len(helps) == 2
    assert helps[0] == helps[1] == MESH_HELP
    for axis in ("data", "seq", "model", "pipe"):
        assert axis in MESH_HELP


# -- stranded devices ---------------------------------------------------------

def test_build_mesh_warns_loudly_about_stranded_devices(caplog):
    with caplog.at_level(logging.WARNING, logger="ml_recipe_tpu.parallel.mesh"):
        mesh = build_mesh("data:2,pipe:2")
    assert any(
        "STRANDED" in rec.message and rec.levelno == logging.WARNING
        for rec in caplog.records
    )
    assert unused_device_count(mesh) == 4
    plan = ParallelPlan.from_mesh(mesh)
    assert plan.unused_devices == 4


def test_plan_topology_accessors():
    plan = ParallelPlan.from_spec("data:2,pipe:2")
    assert plan.describe() == {"pipe": 2, "data": 2}
    assert (plan.data_size, plan.pipe_size) == (2, 2)
    assert (plan.seq_size, plan.model_size) == (1, 1)
    assert not plan.single_device
    full = ParallelPlan.from_spec(None)
    assert full.unused_devices == 0 and full.data_size == 8


# -- plan-derived layouts: one source of truth --------------------------------

@pytest.mark.parametrize("mesh_spec", ["data:4", "data:2,pipe:2"])
def test_plan_layouts_single_source_of_truth(tmp_path, mesh_spec):
    """Trainer opt-state placement, batch placement, checkpoint manifest
    and the HBM pre-flight report all report the layouts the ONE
    ParallelPlan derives — including under a pipe-bearing mesh."""
    trainer, _ = _make_trainer(
        tmp_path, mesh_spec=mesh_spec, dropout=0.0, batch_split=2,
        optimizer_sharding="zero1", zero_min_size=0,
        sharded_checkpoint=True,
    )
    plan = trainer.plan
    assert plan.describe() == dict(
        zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)
    )

    # (a) the live optimizer state's shardings == the plan's derivation
    # (stage_pipe mirrors the trainer: pipe-bearing meshes default to
    # stage-local trunk storage, ISSUE-19)
    from ml_recipe_tpu.parallel.sharding import zero_pad_tree

    stage_pipe = trainer._stage_param_specs is not None
    assert stage_pipe == (plan.pipe_size > 1)
    zplan = plan.zero1(trainer.params, min_size=0, stage_pipe=stage_pipe)
    state_shapes = jax.eval_shape(
        lambda p: trainer.optimizer.init(zero_pad_tree(p, zplan)),
        trainer.params,
    )
    want = plan.opt_state_shardings(state_shapes, zero1=True, min_size=0,
                                    stage_pipe=stage_pipe)
    got = jax.tree_util.tree_map(lambda x: x.sharding, trainer.opt_state)
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert w.spec == g.spec, (w, g)

    # (b) batch placement (the same make_global_array the predictor and
    # engine call) matches the plan's batch spec — rows over data, never
    # over pipe
    from ml_recipe_tpu.parallel import make_global_array

    batch = {"input_ids": np.zeros((8, 16), np.int32)}
    placed = make_global_array(batch, trainer.mesh)
    assert placed["input_ids"].sharding.spec == plan.batch_spec(ndim=2)

    # (c) the sharded manifest records the plan topology and the data-axis
    # shard count the zero1 layout implies
    from ml_recipe_tpu.train.checkpoint import peek_checkpoint_layout

    ckpt = tmp_path / f"plan_{mesh_spec.replace(':', '_')}.ch"
    trainer.save_state_dict(ckpt)
    layout = peek_checkpoint_layout(ckpt)
    assert layout["mesh_axes"] == plan.describe()
    assert layout["opt_sharding"] == "zero1"
    # widest leaf: data-axis ZeRO shards x stage-local pipe shards
    assert layout["shards"] == plan.data_size * plan.pipe_size
    if plan.pipe_size > 1:
        assert layout["pipe_schedule"] == "gpipe"
        assert layout["pipe_param_layout"] == "stage"
    else:
        assert layout["pipe_schedule"] is None
        assert layout["pipe_param_layout"] is None

    # (d) the pre-flight report carries the plan topology + stranded count
    # (mocked memory analysis — CPU reports no real limit)
    class _FakeCompiled:
        def memory_analysis(self):
            class A:
                temp_size_in_bytes = 10
                argument_size_in_bytes = 10
                output_size_in_bytes = 10
                alias_size_in_bytes = 10
                generated_code_size_in_bytes = 0
            return A()

    trainer._preflight_done = False
    report = trainer.preflight_train_step(
        None, None, compile_fn=lambda t: _FakeCompiled(),
        limit_bytes=10**9,
    )
    assert report["mesh_axes"] == plan.describe()
    assert report["mesh_unused_devices"] == plan.unused_devices
    # (e) pipe-bearing plans name the stage->layer assignment, the
    # schedule and the per-stage param bytes (ISSUE-19 satellite)
    assert report["param_bytes"] > 0
    if plan.pipe_size > 1:
        assert report["pipe_schedule"] == "gpipe"
        assert report["pipe_param_layout"] == "stage"
        assert report["pipe_stage_layers"] == {
            "stage_0": "layer_0..layer_0", "stage_1": "layer_1..layer_1",
        }
        assert len(report["pipe_stage_param_bytes"]) == 2
        assert all(v > 0 for v in report["pipe_stage_param_bytes"].values())
    else:
        assert report["pipe_schedule"] is None
        assert report["pipe_param_layout"] is None


# -- pipeline parity ----------------------------------------------------------

def test_pipeline_forward_matches_sequential(tmp_path):
    """The shard_map GPipe encoder + head twins reproduce model.apply on
    every micro-batch (deterministic) — the drift pin between
    parallel/pipeline.py and models/{encoder,qa_model}.py."""
    t, _ = _make_trainer(tmp_path, mesh_spec="data:2,pipe:2", dropout=0.0,
                         n_epochs=1, batch_split=2)
    inputs, labels = next(iter(t.train_dataloader))
    micro_in = t._split_micro(inputs)
    G = t.batch_split
    encode = make_pipeline_encoder(
        t.model, t.plan, batch_split=G, deterministic=True
    )
    with t.mesh:
        dev = t._global_batch(micro_in, leading_accum=True)
        seq_out, pooled = jax.jit(
            lambda p, d: encode(p, d, jax.random.key(0))
        )(t.params, dev)
        for i in range(G):
            mi = {k: jnp.asarray(v[i]) for k, v in micro_in.items()}
            ref = t.model.apply(
                {"params": t.params}, **mi, deterministic=True
            )
            preds = apply_qa_heads(
                t.model, t.params, seq_out[i], pooled[i],
                mi["attention_mask"], deterministic=True,
                dropout_rng=jax.random.key(1),
            )
            for k in ref:
                # tight bound (observed ~1e-6): this parity IS the drift
                # pin between the pipeline's module twins and
                # models/{encoder,qa_model} — keep it sharp
                np.testing.assert_allclose(
                    np.asarray(ref[k]), np.asarray(preds[k]),
                    rtol=1e-5, atol=1e-5,
                    err_msg=f"micro {i} head {k} diverges",
                )


def test_validate_pipeline_plan_errors(tmp_path):
    t, _ = _make_trainer(tmp_path, mesh_spec="data:4", dropout=0.0)
    plan3 = ParallelPlan.from_spec("data:1,pipe:3")  # 2 layers % 3 != 0
    with pytest.raises(ValueError, match="equal contiguous stages"):
        validate_pipeline_plan(plan3, t.model, batch_split=2)
    # the error must point long-context users at the composed
    # streaming-ring path and record the follow-up (ISSUE 20)
    with pytest.raises(NotImplementedError,
                       match="composed streaming-ring.*ISSUE 20"):
        validate_pipeline_plan(
            ParallelPlan.from_spec("pipe:2,seq:2"), t.model, batch_split=2
        )
    # pipe x model composes since ISSUE-19 (stage specs keep their TP dims)
    validate_pipeline_plan(
        ParallelPlan.from_spec("pipe:2,model:2"), t.model, batch_split=2
    )
    with pytest.raises(ValueError, match="--pipe_schedule"):
        validate_pipeline_plan(
            ParallelPlan.from_spec("data:1,pipe:2"), t.model,
            batch_split=2, schedule="interleaved",
        )
    assert stage_layer_count(12, 4) == 3


# -- bubble accounting --------------------------------------------------------

def test_bubble_fraction_math():
    assert modeled_bubble_fraction(1, 4) == 0.0
    assert modeled_bubble_fraction(2, 1) == 0.5
    assert modeled_bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert modeled_bubble_fraction(4, 8) == pytest.approx(3 / 11)

    # ideal GPipe timings reproduce the model exactly at every point
    K, c = 2, 0.010
    times = {m: c * (m + K - 1) / m for m in (1, 2, 4, 8)}
    meas = measured_bubble_fractions(times, K)
    for m in times:
        assert meas[m] == pytest.approx(modeled_bubble_fraction(K, m), abs=1e-9)

    # a sequential (no-overlap) schedule's constant step time does NOT
    # produce the decreasing model curve — the instrument has teeth
    flat = {m: c for m in (1, 2, 4, 8)}
    meas_flat = measured_bubble_fractions(flat, K)
    assert abs(meas_flat[1] - modeled_bubble_fraction(K, 1)) > 0.1


def _schedule_loops(jaxpr):
    """``(ticks, stage switches a tick)`` of every schedule loop in a
    program: a ``scan`` whose body itself hands activations to the next pipe
    rank (``ppermute``), wherever it is nested."""
    def subjaxprs(eqn):
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield inner

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            if any(e.primitive.name == "ppermute" for e in body.eqns):
                found.append((int(eqn.params["length"]), sum(
                    e.primitive.name == "cond" for e in body.eqns)))
        for inner in subjaxprs(eqn):
            found.extend(_schedule_loops(inner))
    return found


def test_pipe_schedule_overlap_is_real():
    """ISSUE-15 acceptance: the schedule overlaps stages. COUNTED from the
    program, not timed: the step the trainer builds for m micro-batches
    runs its schedule loops for K - 1 + m ticks, each rank applying ONE
    stage a tick, where a sequential run applies m x K stages one after
    the other. In units of one stage over the whole batch a step so costs
    (K - 1 + m) / m, which gives the (K-1)/(K-1+m) bubble curve exactly; a
    sequential implementation's K is flat (test_bubble_fraction_math)."""
    from ml_recipe_tpu.data.bucketing import synthetic_qa_batch
    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import QAModel
    from ml_recipe_tpu.models.config import EncoderConfig
    from ml_recipe_tpu.parallel.pipeline import make_pipeline_encoder
    from ml_recipe_tpu.train import Trainer
    from ml_recipe_tpu.train.optim import build_optimizer

    class TP:
        loss = "smooth"; smooth_alpha = 0.01; focal_alpha = 1; focal_gamma = 2
        w_start = 1; w_end = 1; w_start_reg = 1; w_end_reg = 1; w_cls = 1
        lr = 1e-5; weight_decay = 1e-4; warmup_coef = 0.0
        optimizer = "adamw"; finetune = False

    cfg = EncoderConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=256, max_position_embeddings=160, num_labels=5,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    )
    B, L, K = 16, 128, 2
    mesh = build_mesh("data:1,pipe:2")
    model = QAModel(cfg, mesh=mesh)
    inputs, labels = synthetic_qa_batch(B, L)
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32)
    )["params"]
    times = {}
    for m in (1, 2, 4):
        tr = Trainer(
            model=model, params=params,
            loss=build_loss(TP()), collate_fun=None, trainer_params=None,
            mesh=mesh, batch_split=m, seed=0, train_batch_size=B,
            hbm_preflight=False, pipe_param_sharding="replicated",
        )
        tr.optimizer, tr.scheduler, tr._schedule_count = build_optimizer(
            TP(), tr.params, num_training_steps=100, max_grad_norm=None,
            warmup_coef=0.0,
        )
        tr.init_opt_state()
        di = tr._global_batch(tr._split_micro(inputs), leading_accum=True)
        dl = tr._global_batch(tr._split_micro(labels), leading_accum=True)
        with mesh:
            loops = _schedule_loops(jax.make_jaxpr(tr._build_train_step())(
                tr.params, tr.opt_state, di, dl, 0).jaxpr)
            encode = make_pipeline_encoder(
                model, tr.plan, batch_split=m, deterministic=True)
            forward = _schedule_loops(jax.make_jaxpr(encode)(
                tr.params, di, jax.random.key(0)).jaxpr)
        # the step: a forward and a backward loop, K - 1 + m ticks each
        assert len(loops) >= 2 and {t for t, _ in loops} == {K - 1 + m}, loops
        # a tick: each rank runs exactly one stage (one K-way switch)
        assert forward == [(K - 1 + m, 1)], forward
        ticks, stages_a_tick = forward[0]
        assert m == 1 or ticks * stages_a_tick < m * K  # the sequential run
        times[m] = ticks * stages_a_tick / m

    meas = measured_bubble_fractions(times, K)
    assert meas[1] > meas[2] > meas[4], (times, meas)
    for m in (1, 2, 4):
        assert meas[m] == pytest.approx(
            modeled_bubble_fraction(K, m), abs=1e-9), (m, times, meas)

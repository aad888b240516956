"""The seams of ``ml_recipe_tpu/train/step.py``: the three layouts of the
accumulated gradient (``GradCarry``) against the plain tree arithmetic they
stand for, the table ``choose_carry`` picks from, a step built and run from a
hand-filled ``StepSpec`` with no ``Trainer`` (and no QA model), and the one
pre-flight loop on the bucketed path (the plain path's twins are in
``tests/test_mla_moe.py``).
"""

import ast
import dataclasses
import logging
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ml_recipe_tpu.parallel.plan import ParallelPlan
from ml_recipe_tpu.parallel.sharding import zero1_bucket_plan
from ml_recipe_tpu.train import step as step_lib
from ml_recipe_tpu.train.step import BucketedCarry, FlatCarry, GradCarry

from test_trainer import MAX_SEQ_LEN, _make_trainer

STEP_PY = Path(step_lib.__file__)


def _params():
    """Mixed dtypes, a scalar leaf, and leaves small enough to share a
    bucket beside one that fills its own."""
    k = jax.random.split(jax.random.key(0), 6)
    return {
        "a": {"bias": jax.random.normal(k[0], (4,), jnp.bfloat16),
              "kernel": jax.random.normal(k[1], (3, 4), jnp.float32)},
        "b": {"kernel": jax.random.normal(k[2], (40,), jnp.float32),
              "scale": jax.random.normal(k[3], (), jnp.float32)},
        "c": {"w": jax.random.normal(k[4], (2, 3), jnp.bfloat16)},
    }


def _grads(seed, params):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, l.shape, jnp.float32).astype(l.dtype)
        for k, l in zip(keys, leaves)])


FROZEN = {"a": {"bias": True, "kernel": False},
          "b": {"kernel": True, "scale": False}, "c": {"w": True}}


def _carry(layout, params, trainable=None):
    if layout == "flat":
        return FlatCarry(params, trainable)
    if layout == "per_tensor":
        return GradCarry(params, trainable)
    buckets = tuple(zero1_bucket_plan(params, bucket_mb=100 / 2 ** 20))
    assert len(buckets) > 1 and any(b.hi - b.lo > 1 for b in buckets)
    return BucketedCarry(params, trainable, buckets)


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _assert_trees_equal(a, b, **tol):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), **tol)


LAYOUTS = ["flat", "bucketed", "per_tensor"]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_to_tree_undoes_from_tree_in_the_parameters_dtypes(layout):
    params = _params()
    grads = _grads(1, params)
    carry = _carry(layout, params)
    acc = carry.from_tree(grads)
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(acc))
    _assert_trees_equal(carry.to_tree(acc, params), grads, rtol=0, atol=0)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_add_twice_is_the_f32_tree_sum(layout):
    params = _params()
    g1, g2 = _grads(1, params), _grads(2, params)
    carry = _carry(layout, params)
    acc = carry.add(carry.add(carry.zeros(), g1), g2)
    want = jax.tree_util.tree_map(jnp.add, _f32(g1), _f32(g2))
    _assert_trees_equal(carry.to_tree(acc, _f32(params)), want,
                        rtol=0, atol=0)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_sq_norm_is_the_trees_global_norm_squared(layout):
    params = _params()
    grads = _grads(3, params)
    carry = _carry(layout, params)
    got = float(carry.sq_norm(carry.from_tree(grads)))
    assert got == pytest.approx(
        float(optax.global_norm(_f32(grads))) ** 2, rel=1e-6)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_mask_frozen_zeroes_exactly_the_frozen_leaves(layout):
    """... and an inf there vanishes: replaced, not multiplied."""
    params = _params()
    grads = _grads(4, params)
    grads["a"]["kernel"] = grads["a"]["kernel"].at[1, 2].set(jnp.inf)
    grads["b"]["scale"] = jnp.float32(jnp.nan)
    carry = _carry(layout, params, FROZEN)
    masked = carry.mask_frozen(carry.from_tree(grads))
    assert np.isfinite(float(carry.sq_norm(masked)))
    want = jax.tree_util.tree_map(
        lambda g, keep: g if keep else jnp.zeros_like(g), grads, FROZEN)
    _assert_trees_equal(carry.to_tree(masked, params), want, rtol=0, atol=0)
    # no mask: nothing is touched
    acc = carry.from_tree(grads)
    same = _carry(layout, params).mask_frozen(acc)
    assert all(x is y for x, y in zip(jax.tree_util.tree_leaves(same),
                                      jax.tree_util.tree_leaves(acc)))


# -- the chooser ---------------------------------------------------------------------

@pytest.mark.parametrize("mesh,how,layout,bucketed,says", [
    ("data:4", {}, FlatCarry, False, None),
    ("data:1", {}, FlatCarry, False, None),
    ("data:2,model:2", {}, GradCarry, False, None),
    ("data:2,pipe:2", {"stage_local": True}, GradCarry, False, None),
    ("data:2,pipe:2", {}, FlatCarry, False, None),
    ("data:4", {"zero1": True}, FlatCarry, False, None),
    ("data:4", {"zero1": True, "overlap": "bucketed"}, BucketedCarry, True,
     "gradient bucket(s)"),
    ("data:4", {"overlap": "bucketed"}, FlatCarry, False,
     "without an active zero1 layout"),
    ("data:2,model:2", {"zero1": True, "overlap": "bucketed"}, GradCarry,
     False, "accumulate per tensor"),
    ("data:2,pipe:2", {"zero1": True, "overlap": "bucketed"}, FlatCarry,
     False, "under pipeline parallelism"),
    ("data:1", {"flat_carry": False}, GradCarry, False, None),
    ("data:4", {"flat_carry": False, "zero1": True, "overlap": "bucketed"},
     GradCarry, False, "bucketing is inert"),
], ids=["data_only", "one_chip", "model2", "stage_local", "pipe_replicated",
        "zero1_off", "zero1_bucketed", "bucketed_without_zero1",
        "bucketed_on_model2", "bucketed_under_pipe", "withdrawn",
        "withdrawn_under_bucketed"])
def test_choose_carry(mesh, how, layout, bucketed, says, caplog):
    plan = ParallelPlan.from_spec(mesh)
    params = _params()
    how = dict(how)
    zero_plan = plan.zero1(params, min_size=0) if how.pop("zero1", False) \
        else None
    with caplog.at_level(logging.INFO, "ml_recipe_tpu.train.step"):
        got, buckets = step_lib.choose_carry(
            plan, params, zero_plan=zero_plan, bucket_mb=100 / 2 ** 20, **how)
    assert got is layout and got.flat == (layout is not GradCarry)
    assert (len(buckets) > 1) == bucketed and (bucketed or buckets == ())
    lines = [r.getMessage() for r in caplog.records
             if r.name == "ml_recipe_tpu.train.step"]
    assert (lines == []) if says is None else any(says in l for l in lines)


# -- a step from a hand-filled record: no Trainer, no QA model -----------------------

class _Line:
    """``y = x @ w + b`` with dropout on x: the least a model is to the step."""

    def apply(self, variables, x, *, deterministic, rngs):
        p = variables["params"]
        if not deterministic:
            keep = jax.random.bernoulli(rngs["dropout"], 0.9, x.shape)
            x = jnp.where(keep, x / 0.9, 0.0)
        return x @ p["w"] + p["b"]


class _Mse:
    """Mean squared error with the normaliser the step may hand in (the
    data island takes it from the global micro-batch's labels)."""

    def value_structure(self):
        return {"loss": 0.0}

    def denominators(self, labels):
        return {"rows": jnp.float32(labels["y"].shape[0])}

    def __call__(self, preds, labels, denominators=None):
        rows = (self.denominators(labels) if denominators is None
                else denominators)["rows"]
        loss = jnp.sum((preds - labels["y"]) ** 2) / rows
        return loss, {"loss": loss}


def _toy(mesh, **spec):
    plan = ParallelPlan.from_spec(mesh)
    params = {"w": jnp.full((3, 2), 0.5, jnp.float32),
              "b": jnp.zeros((2,), jnp.float32)}
    optimizer = optax.sgd(0.1)
    spec = step_lib.StepSpec(
        model=_Line(), loss=_Mse(), optimizer=optimizer, plan=plan,
        batch_split=2, seed=3, max_grad_norm=1.0,
        carry=step_lib.choose_carry(plan, params)[0], **spec)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 4, 3)).astype(np.float32)      # [G, B, ...]
    y = rng.normal(size=(2, 4, 2)).astype(np.float32)
    return spec, params, optimizer.init(params), {"x": x}, {"y": y}


def test_a_step_from_a_hand_filled_spec():
    spec, params, opt_state, inputs, labels = _toy(
        "data:1", scheduler=lambda step: 0.1)
    step = jax.jit(step_lib.build_step(spec))
    assert step.__name__ == "train_step"
    new_params, _, values = step(params, opt_state, inputs, labels, 0)
    assert set(values) == {"loss", "lr"} and float(values["lr"]) == \
        pytest.approx(0.1)

    # the same arithmetic by hand: mean of the micro-batches' gradients under
    # the step's own keys, clipped to norm 1, one SGD step
    keys = jax.random.split(
        jax.random.fold_in(jax.random.key(3, impl="rbg"), 0), 2)

    def micro(p, i):
        preds = spec.model.apply({"params": p}, inputs["x"][i],
                                 deterministic=False,
                                 rngs={"dropout": keys[i]})
        return spec.loss(preds, {"y": labels["y"][i]})[0]

    losses, grads = zip(*(jax.value_and_grad(micro)(params, i)
                          for i in range(2)))
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, *grads)
    scale = 1.0 / max(float(optax.global_norm(mean)), 1.0)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.1 * scale * g,
                                  params, mean)
    assert float(values["loss"]) == pytest.approx(float(sum(losses)) / 2,
                                                  rel=1e-6)
    _assert_trees_equal(new_params, want, rtol=1e-5, atol=1e-6)

    for i in range(1, 30):
        new_params, opt_state, values = step(
            new_params, opt_state, inputs, labels, i)
    assert float(values["loss"]) < 0.8 * float(sum(losses)) / 2


def test_the_hand_filled_step_runs_as_a_data_island():
    """Dropout off (the island's chips draw their own hidden masks): the
    ``data:2`` island gives the one-device step."""
    out = {}
    for mesh in ("data:1", "data:2"):
        spec, params, opt_state, inputs, labels = _toy(mesh)
        spec_model = types.SimpleNamespace(
            apply=lambda v, x, *, deterministic, rngs: _Line().apply(
                v, x, deterministic=True, rngs=rngs))
        spec = dataclasses.replace(spec, model=spec_model)
        assert step_lib.exchanges_once(spec) == (mesh == "data:2")
        with spec.plan.mesh:
            out[mesh] = jax.device_get(jax.jit(step_lib.build_step(spec))(
                params, opt_state, inputs, labels, 0))
    _assert_trees_equal(out["data:2"][0], out["data:1"][0],
                        rtol=1e-6, atol=1e-7)
    assert float(out["data:2"][2]["loss"]) == pytest.approx(
        float(out["data:1"][2]["loss"]), rel=1e-6)


def test_step_py_stands_alone():
    """It imports nothing from the Trainer's module, and no function in it
    is long enough to hide a second step body."""
    tree = ast.parse(STEP_PY.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert "trainer" not in (node.module or "") and all(
                a.name != "trainer" for a in node.names), ast.dump(node)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            assert node.end_lineno - node.lineno + 1 <= 120, node.name


# -- the one pre-flight loop, on the bucketed path -----------------------------------

def _analysis(need):
    return types.SimpleNamespace(memory_analysis=lambda: types.SimpleNamespace(
        temp_size_in_bytes=need, argument_size_in_bytes=0,
        output_size_in_bytes=0, alias_size_in_bytes=0))


def _bucketed_trainer(tmp_path, **kw):
    return _make_trainer(tmp_path, batch_split=1,
                         length_buckets=[24, MAX_SEQ_LEN], **kw)[0]


def test_bucket_preflight_answers_a_compile_time_refusal(tmp_path):
    trainer = _bucketed_trainer(tmp_path)
    loader = trainer.train_dataloader
    asked = []

    def compile_fn(t, seq, batch):
        asked.append((seq, t.batch_split))
        if seq == MAX_SEQ_LEN and t.batch_split < 2:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
                "of memory in memory space hbm. Used 16.64G of 15.75G hbm.")
        return _analysis(30)

    report = trainer.preflight_bucket_steps(
        compile_fn=compile_fn, limit_bytes=10 ** 9)
    # the refused bucket stops the pass; every bucket is asked again
    assert asked == [(MAX_SEQ_LEN, 1), (MAX_SEQ_LEN, 2), (24, 2)]
    assert trainer.batch_split == report["batch_split"] == 2
    assert report["applied"] and report["compile_refused_at"] == [1]
    assert loader.batch_multiple == 2 * 8       # batch_split x data axis
    assert all(v % 16 == 0 for v in loader.batch_sizes.values())
    assert [b["bytes"] for b in report["buckets"]] == [30, 30]
    assert trainer.flat_carry and "flat_carry_withdrawn_at" not in report


def test_bucket_preflight_withdraws_the_flat_carry(tmp_path):
    """A bucket over the limit by less than the flat carry's copy keeps its
    micro-batch and the loader's batch sizes; the step accumulates per
    tensor."""
    trainer = _bucketed_trainer(tmp_path)
    loader = trainer.train_dataloader
    sizes = dict(loader.batch_sizes)
    limit = 10 ** 9
    copy = trainer._preflight_pipe_fields()["param_bytes"]
    asked = []

    def compile_fn(t, seq, batch):
        asked.append((seq, t.batch_split, t.flat_carry))
        return _analysis(limit + copy // 2 if len(asked) == 1 else 30)

    report = trainer.preflight_bucket_steps(
        compile_fn=compile_fn, limit_bytes=limit)
    assert asked == [(MAX_SEQ_LEN, 1, True), (MAX_SEQ_LEN, 1, False),
                     (24, 1, False)]
    assert (trainer.batch_split, trainer.flat_carry) == (1, False)
    assert report["flat_carry_withdrawn_at"] == 1 and not report["applied"]
    assert loader.batch_sizes == sizes
    # the rebuilt step really is the per-tensor one
    trainer._build_train_step()
    assert trainer.flat_carry is False


@pytest.mark.parametrize("message,split", [
    ("INVALID_ARGUMENT: something else", 1),
    ("RESOURCE_EXHAUSTED: out of memory", 2),   # data:8, batch 16: the last
], ids=["no_oom", "oom_at_the_last_split"])
def test_bucket_preflight_lets_other_compile_errors_through(
        tmp_path, message, split):
    trainer = _make_trainer(tmp_path, batch_split=split,
                            length_buckets=[24, MAX_SEQ_LEN])[0]

    def compile_fn(t, seq, batch):
        raise RuntimeError(message)

    with pytest.raises(RuntimeError, match=message.split(":")[0]):
        trainer.preflight_bucket_steps(compile_fn=compile_fn,
                                       limit_bytes=10 ** 9)
    assert trainer.batch_split == split

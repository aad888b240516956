"""The seams of ``ml_recipe_tpu/train/step.py``: the two layouts of the
accumulated gradient (``GradCarry``) against the plain tree arithmetic they
stand for, the table ``choose_carry`` picks from, a step built and run from a
hand-filled ``StepSpec`` with no ``Trainer`` (and no QA model), what the
lowered programs no longer hold (a copy of the whole gradient), and the one
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
from ml_recipe_tpu.train.step import BucketedCarry, GradCarry

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


LAYOUTS = ["bucketed", "per_tensor"]


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

@pytest.mark.parametrize("mesh,how,layout,says", [
    ("data:4", {}, GradCarry, None),
    ("data:1", {}, GradCarry, None),
    ("data:2,model:2", {}, GradCarry, None),
    ("pipe:4", {}, GradCarry, None),
    ("data:2,pipe:2", {}, GradCarry, None),
    ("data:4", {"zero1": True}, GradCarry, None),
    ("data:4", {"zero1": True, "overlap": "bucketed"}, BucketedCarry,
     "gradient bucket(s)"),
    ("data:4", {"overlap": "bucketed"}, GradCarry,
     "without an active zero1 layout"),
    ("data:2,model:2", {"zero1": True, "overlap": "bucketed"}, GradCarry,
     "gradients are sharded"),
    ("data:2,pipe:2", {"zero1": True, "overlap": "bucketed"}, GradCarry,
     "under pipeline parallelism"),
    ("data:8", {}, GradCarry, None),
    ("data:2,seq:2", {"zero1": True}, GradCarry, None),
], ids=["data_only", "one_chip", "model2", "pipe_only", "pipe_replicated",
        "zero1_off", "zero1_bucketed", "bucketed_without_zero1",
        "bucketed_on_model2", "bucketed_under_pipe", "eight_chips",
        "seq2_zero1"])
def test_choose_carry(mesh, how, layout, says, caplog):
    """Per tensor on every mesh; bucketed only where it is asked for AND
    can engage, and a request that cannot says why."""
    plan = ParallelPlan.from_spec(mesh)
    params = _params()
    how = dict(how)
    zero_plan = plan.zero1(params, min_size=0) if how.pop("zero1", False) \
        else None
    with caplog.at_level(logging.INFO, "ml_recipe_tpu.train.step"):
        got, buckets = step_lib.choose_carry(
            plan, params, zero_plan=zero_plan, bucket_mb=100 / 2 ** 20, **how)
    assert got is layout
    assert got.name == ("bucketed" if layout is BucketedCarry
                        else "per_tensor")
    assert (len(buckets) > 1) if layout is BucketedCarry else buckets == ()
    lines = [r.getMessage() for r in caplog.records
             if r.name == "ml_recipe_tpu.train.step"]
    assert (lines == []) if says is None else any(says in l for l in lines)


@pytest.mark.parametrize("how,carry", [
    ({}, "per_tensor"),
    ({"optimizer_sharding": "zero1", "zero_min_size": 0,
      "zero1_overlap": "bucketed",
      "zero1_bucket_mb": 0.001}, "bucketed"),
], ids=["per_tensor", "bucketed"])
def test_the_trainer_says_which_carry_its_step_has(tmp_path, caplog, how,
                                                   carry):
    """Once a layout, however often the step is rebuilt."""
    trainer = _make_trainer(tmp_path, mesh_spec="data:4", batch_split=2,
                            **how)[0]
    assert trainer.grad_carry is None
    with caplog.at_level(logging.INFO, "ml_recipe_tpu.train.trainer"):
        trainer._build_train_step()
        trainer._build_train_step()
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("gradient carry:")]
    assert said == [f"gradient carry: {carry}"]
    assert trainer.grad_carry == carry
    assert (trainer.zero1_bucket_count > 1) == (carry == "bucketed")


# -- the clip on the per-tensor tree -------------------------------------------------

@pytest.mark.parametrize("max_norm", [0.05, 1e3], ids=["clips", "passes"])
def test_the_clip_on_the_tree_is_optax_clip_by_global_norm(max_norm):
    """``clip_gradients`` on the accumulated per-tensor carry against
    ``optax.clip_by_global_norm`` on the same mean gradients."""
    params = _f32(_params())
    g1, g2 = _grads(5, params), _grads(6, params)
    carry = GradCarry(params)
    acc = carry.add(carry.add(carry.zeros(), g1), g2)
    spec = types.SimpleNamespace(batch_split=2, max_grad_norm=max_norm,
                                 use_loss_scale=False)
    got, finite = step_lib.clip_gradients(spec, carry, acc, params, None)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, g1, g2)
    want, _ = optax.clip_by_global_norm(max_norm).update(mean, None)
    assert finite is None
    clipped = float(optax.global_norm(mean)) > max_norm
    assert clipped == (max_norm < 1)
    _assert_trees_equal(got, want, rtol=1e-6, atol=1e-6 * max_norm)


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


# -- what the lowered programs hold ----------------------------------------------------

@pytest.mark.parametrize("mesh_spec,exchanges", [("data:1", 0), ("data:4", 1)],
                         ids=["one_chip", "data4_island"])
def test_no_lowered_step_holds_a_copy_of_the_whole_gradient(
        tmp_path, mesh_spec, exchanges):
    """The micro-batch loop's carry is the parameters' tree: no
    ``concatenate`` in the lowered step makes a vector of the gradient's
    element count (the flat carry made one a micro-batch), and the data
    island hands back a tree whose ``grad_reduce`` sums leaf by leaf."""
    import re

    from test_dp_equivalence import _step_args

    trainer = _make_trainer(tmp_path, mesh_spec=mesh_spec, batch_split=4,
                            train_batch_size=32)[0]
    step = trainer._build_train_step()
    assert trainer.grad_exchanges_per_step == exchanges
    with trainer.mesh:
        # scope names are location attributes, referred to by id
        text = step.lower(*_step_args(trainer)).as_text(debug_info=True)
    leaves = jax.tree_util.tree_leaves(trainer.params)
    n_params = sum(int(np.prod(p.shape)) for p in leaves)
    made = [int(np.prod([int(d) for d in m.group(1).split("x")[:-1]] or [1]))
            for m in re.finditer(
                r"stablehlo\.concatenate.*-> tensor<([\dx]*\w+)>", text)]
    assert all(n < n_params for n in made), (n_params, sorted(made)[-3:])
    # the scan's carry: one f32 accumulator a parameter, in its own shape
    while_types = re.search(r"stablehlo\.while\((.*?)\)\s*:\s*(.*)", text)
    assert while_types is not None
    carried = re.findall(r"tensor<([\dx]*)xf32>", while_types.group(2))
    for p in leaves:
        assert "x".join(str(d) for d in p.shape) in carried, p.shape
    assert f"{n_params}" not in carried

    ids = re.findall(
        r'^(#loc\d+) = loc\("[^"]*grad_reduce/reduce_sum', text, re.M)
    sums = [l for l in text.splitlines() if "stablehlo.reduce" in l
            and any(f"loc({i})" in l for i in ids)]
    if exchanges:
        # one sum over the stacked `data` axis a leaf (the loss values'
        # sums ride the same scope)
        summed = [m for l in sums
                  for m in re.findall(r"\(tensor<4x([\dx]*)xf32>", l)]
        assert sorted(summed) == sorted(
            "x".join(str(d) for d in p.shape) for p in leaves)
    else:
        assert sums == []


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
    assert report["grad_carry"] == "per_tensor"


def test_a_bucket_over_the_limit_raises_batch_split(tmp_path):
    """A bucket over the limit by half a gradient's copy: the per-tensor
    step holds no such copy to give up, so ``batch_split`` rises, the
    loader's batch sizes are rescaled, and the report says which layout the
    checked step has."""
    trainer = _bucketed_trainer(tmp_path)
    loader = trainer.train_dataloader
    limit = 10 ** 9
    copy = trainer._preflight_pipe_fields()["param_bytes"]
    asked = []

    def compile_fn(t, seq, batch):
        asked.append((seq, t.batch_split))
        return _analysis(limit + copy // 2 if len(asked) == 1 else 30)

    report = trainer.preflight_bucket_steps(
        compile_fn=compile_fn, limit_bytes=limit)
    assert asked == [(MAX_SEQ_LEN, 1), (MAX_SEQ_LEN, 2), (24, 2)]
    assert trainer.batch_split == report["batch_split"] == 2
    assert report["applied"] and "compile_refused_at" not in report
    assert report["grad_carry"] == trainer.grad_carry == "per_tensor"
    assert all(v % 16 == 0 for v in loader.batch_sizes.values())


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

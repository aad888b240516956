"""The gated delta rule's Mosaic form (``ops/gated_delta_kernel.py``) in
interpret mode on the CPU, against the XLA form (``ops/gated_delta.py``), which
stays the oracle: the forward's output and kept chunk-start states at rows
that are and are not a whole number of chunks and at two pairs of head widths
(an even number of heads, whose triangles are inverted two at a time, and an
odd one), the block inverse against the XLA form's triangular solve on unit
triangles with ``beta`` near 2 and keys that lie close together, each of the
five gradients against the XLA form's ``custom_vjp`` and against autodiff of
the token-by-token recurrence, right padding, the choice of the form from the
backend and the shapes (and its one log line), the call under a mesh that
shards the batch, and one step of the tiny ``olmo_hybrid`` configuration.
"""

import dataclasses
import logging
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from ml_recipe_tpu.ops import (  # noqa: E402
    gated_delta, gated_delta_kernel, kernel_forms)
from ml_recipe_tpu.parallel import build_mesh  # noqa: E402
from perfbench.harness import reference_olmo_hybrid  # noqa: E402

from test_mla_moe import make_trainer  # noqa: E402
from test_olmo_hybrid import TINY, _operands  # noqa: E402

CHUNK = gated_delta.CHUNK
GRADIENTS = ("q", "k", "v", "g", "beta")


def rule(kernel, chunk=CHUNK):
    """The operator in one form: ``kernel`` None is the XLA form, True the
    Mosaic kernels interpreted."""
    return lambda *ops: gated_delta._gated_delta(*ops, chunk, kernel)


@pytest.fixture
def interpreted(monkeypatch):
    """The choice a TPU makes, with the kernels interpreted."""
    monkeypatch.setattr(gated_delta, "kernel_mode", lambda *widths: True)


@pytest.mark.parametrize("length, heads, d_k, d_v", [
    (64, 4, 32, 64),        # one whole chunk
    (200, 4, 32, 64),       # three whole chunks and a part
    (256, 3, 32, 64),       # four whole chunks; an odd number of heads
    (200, 2, 96, 192),      # the published widths
    (64, 1, 96, 192),
])
def test_the_kernel_forward_is_the_xla_forms_output_and_states(
        length, heads, d_k, d_v):
    ops = _operands(length=length, H=heads, d_k=d_k, d_v=d_v,
                    dtype=jnp.bfloat16)
    want, (*_, want_states) = gated_delta._fwd(*ops, CHUNK, None)
    got, (*_, states) = gated_delta._fwd(*ops, CHUNK, True)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    assert states.shape == want_states.shape == (
        -(-length // CHUNK), 2, heads, d_k, d_v)
    # one rounding of the output: at most a bf16 step apart where an f32
    # sum's last bits fell on either side of it
    largest = float(jnp.abs(want.astype(jnp.float32)).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)
                         ).max()) <= 2.0 ** -7 * largest
    assert float(jnp.mean(got != want)) < 1e-2
    assert np.allclose(states, want_states, rtol=1e-5, atol=1e-5)
    # the call that keeps no state writes the same output
    assert np.array_equal(np.asarray(rule(True)(*ops)), np.asarray(got))


@pytest.mark.parametrize("chunk", [16, 32])
def test_the_kernel_takes_any_power_of_two_chunk(chunk):
    ops = _operands(length=100, H=2, d_k=32, d_v=32)
    assert np.allclose(rule(True, chunk)(*ops), rule(None, chunk)(*ops),
                       atol=2e-6)


@pytest.mark.parametrize("spread", [1.0, 0.1, 0.02])
@pytest.mark.parametrize("side_by_side", [False, True])
def test_the_block_inverse_is_the_solve(spread, side_by_side):
    """Seeded unit lower triangles as a chunk makes them, with ``beta`` near 2
    and keys within ``spread`` of one direction (condition numbers of 1e2 to
    3e3): ``_inverse(A) rhs`` lies as close to the float64 solution as
    ``_solve(A, rhs)`` does, to a factor of four."""
    C = CHUNK
    for seed in range(4):
        rng = np.random.default_rng(seed)
        k = rng.normal(size=(1, 96)) + spread * rng.normal(size=(C, 96))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        k = np.asarray(jnp.asarray(k, jnp.bfloat16).astype(jnp.float32),
                       np.float64)
        beta = 2 - 10 ** rng.uniform(-3, -1, size=C)
        c = np.cumsum(-10 ** rng.uniform(-4, -1, size=C))
        A = np.tril(beta[:, None] * np.exp(c[:, None] - c[None, :])
                    * (k @ k.T), -1)
        rhs = rng.normal(size=(C, 32))
        want = np.linalg.solve(np.eye(C) + A, rhs)
        A32, rhs32 = jnp.asarray(A, jnp.float32), jnp.asarray(rhs, jnp.float32)
        with jax.default_matmul_precision("highest"):
            solved = np.asarray(gated_delta._solve(A32, rhs32), np.float64)
            if side_by_side:
                other = jnp.asarray(np.tril(rng.normal(size=(C, C)), -1),
                                    jnp.float32)
                T = gated_delta_kernel._inverse(
                    jnp.concatenate([other, A32], axis=1))[:, C:]
            else:
                T = gated_delta_kernel._inverse(A32)
            inverted = np.asarray(T @ rhs32, np.float64)
        assert np.array_equal(np.asarray(T), np.tril(np.asarray(T)))
        scale = np.abs(want).max()
        assert np.abs(inverted - want).max() <= 4 * max(
            np.abs(solved - want).max(), 1e-6 * scale), (seed, spread)


@pytest.fixture(scope="module")
def gradients():
    """``{form: the five gradients}`` of one seeded weighted sum, at two whole
    chunks and a part, three heads."""
    ops = _operands(seed=1, length=150, H=3, d_k=32, d_v=64)
    weigh = jnp.asarray(np.random.default_rng(2).normal(size=ops[2].shape),
                        jnp.float32)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * weigh)  # noqa: E731
    return {name: jax.grad(loss(f), argnums=range(5))(*ops)
            for name, f in (("kernel", rule(True)), ("xla", rule(None)),
                            ("recurrence", reference_olmo_hybrid.delta_rule))}


@pytest.mark.parametrize("oracle", ["xla", "recurrence"])
@pytest.mark.parametrize("name", GRADIENTS)
def test_every_gradient_of_the_kernel_backward(gradients, name, oracle):
    at = GRADIENTS.index(name)
    got, want = gradients["kernel"][at], gradients[oracle][at]
    assert got.shape == want.shape and got.dtype == want.dtype
    bound = 1e-5 if oracle == "xla" else 1e-4
    assert float(jnp.abs(got - want).max()) < bound * float(
        jnp.abs(want).max())


def test_the_kernel_backward_keeps_and_returns_what_the_xla_form_does():
    ops = _operands(length=128, dtype=jnp.bfloat16, H=2, d_k=32, d_v=64)
    kept = lambda kernel: sorted(  # noqa: E731
        (tuple(x.shape), str(x.dtype)) for x in jax.tree_util.tree_leaves(
            jax.vjp(rule(kernel), *ops)[1]) if hasattr(x, "shape"))
    assert kept(True) == kept(None)
    out, vjp = jax.vjp(rule(True), *ops)
    assert [g.dtype for g in vjp(jnp.ones_like(out))] == [
        x.dtype for x in ops]


def test_right_padding_is_harmless_to_the_kernel_too():
    """Whatever stands right of a row's end, with keys of unit length as a
    layer makes them: the kernel forms a chunk's inverse whole, so only a
    later token that overflowed float32 could reach an earlier one (as ``0 x
    inf``), and ``|A| <= 2`` cannot."""
    ops = _operands(length=100, H=2, d_k=32, d_v=64)
    out = rule(True)(*ops)
    garbage = (ops[0].at[:, 70:].set(7.0), ops[1].at[:, 70:].set(32 ** -0.5),
               ops[2].at[:, 70:].set(7.0), ops[3].at[:, 70:].set(-3.0),
               ops[4].at[:, 70:].set(1.9))
    assert np.array_equal(np.asarray(rule(True)(*garbage)[:, :70]),
                          np.asarray(out[:, :70]))
    grads = jax.grad(lambda *a: jnp.sum(rule(True)(*a)[:, :70]),
                     argnums=range(5))(*ops)
    assert all(float(jnp.abs(g[:, 70:]).max()) == 0.0 for g in grads)


def test_the_form_follows_the_backend_and_the_shapes(monkeypatch, caplog):
    """Off a TPU the XLA form, and nothing is logged; on one the kernels for
    the shapes they take, and ONE warning a reason where they refuse."""
    def mode(d_k, d_v, L=8192, **how):
        row = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
            (1, L, 30, d), jnp.bfloat16)
        return gated_delta.kernel_mode(row(d_k), row(d_v), **how)

    kernel_forms.log_refusal.cache_clear()
    with caplog.at_level(logging.WARNING, "ml_recipe_tpu.ops.gated_delta"):
        assert jax.default_backend() == "cpu"
        assert mode(96, 192) is None and mode(8, 16) is None
        assert caplog.records == []
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert mode(96, 192) is False and mode(64, 128) is False
        assert mode(96, 192, L=100_000) is False
        assert caplog.records == []
        for _ in range(3):      # a layer each
            assert mode(8, 16) is None
        assert mode(96, 192, chunk=48) is None
        assert mode(96, 192, L=200_000) is None
    assert [r.getMessage() for r in caplog.records] == [
        f"gated delta rule: the Mosaic kernels refuse this shape ({why}); "
        f"running the XLA form." for why in (
            "head widths 8 / 16 are not multiples of 32",
            "chunk 48 is not a power of two of at least 16",
            "a head's blocks at 200000 tokens and widths 96 / 192 pass 12 "
            "MiB of VMEM")]
    kernel_forms.log_refusal.cache_clear()


@pytest.mark.parametrize("length, heads", [
    (8192, 6), (16384, 6), (32768, 2), (131072, 1), (196608, None)])
def test_a_longer_row_takes_fewer_heads_a_grid_step(length, heads):
    """A row's ``[N, C]`` floats stay in VMEM for the whole row, so the
    heads a step follow the row's length; 30 heads of 96 / 192 in bf16."""
    assert gated_delta_kernel.heads_a_step(
        30, length // CHUNK, CHUNK, 96, 192, 2) == heads
    assert gated_delta_kernel.heads_a_step(7, 128, CHUNK, 96, 192, 2) == 1


def test_the_entry_point_on_a_cpu_is_the_xla_form():
    ops = _operands(length=70, H=2, d_k=32, d_v=64)
    text = jax.jit(gated_delta.gated_delta_rule).lower(*ops).as_text()
    assert "gated_delta_fwd" not in text
    assert np.array_equal(np.asarray(gated_delta.gated_delta_rule(*ops)),
                          np.asarray(rule(None)(*ops)))


@pytest.mark.parametrize("spec, sharded", [
    (None, False), ("data:1", False), ("data:2", True)])
def test_under_a_mesh_the_kernel_runs_a_shard_of_the_batch(
        interpreted, spec, sharded):
    mesh = build_mesh(spec) if spec else None
    ops = _operands(length=100, H=2, d_k=32, d_v=64)
    call = jax.jit(lambda *a: gated_delta.over_batch_shards(mesh, *a))
    assert ("shard_map" in str(jax.make_jaxpr(call)(*ops))) == sharded
    assert np.allclose(call(*ops), rule(None)(*ops), atol=2e-6)
    grads = jax.grad(lambda *a: jnp.sum(call(*a) ** 2), argnums=range(5))(
        *ops)
    want = jax.grad(lambda *a: jnp.sum(rule(None)(*a) ** 2),
                    argnums=range(5))(*ops)
    for name, g, w in zip(GRADIENTS, grads, want):
        assert float(jnp.abs(g - w).max()) < 1e-5 * float(
            jnp.abs(w).max()), name


def test_a_batch_the_mesh_does_not_divide_takes_the_xla_form(
        interpreted, caplog):
    kernel_forms.log_refusal.cache_clear()
    ops = _operands(B=3, length=70, H=2, d_k=32, d_v=64)
    with caplog.at_level(logging.WARNING, "ml_recipe_tpu.ops.gated_delta"):
        out = gated_delta.over_batch_shards(build_mesh("data:2"), *ops)
    assert np.array_equal(np.asarray(out), np.asarray(rule(None)(*ops)))
    ours = [r.getMessage() for r in caplog.records
            if r.name == "ml_recipe_tpu.ops.gated_delta"]
    assert len(ours) == 1 and "do not divide" in ours[0]
    kernel_forms.log_refusal.cache_clear()


def test_a_step_with_the_kernel_is_the_step_with_the_xla_form(
        tmp_path, monkeypatch):
    """The tiny ``olmo_hybrid`` configuration through ``Trainer`` and
    ``build_step`` with ``remat`` on, the cell's shape of step: the primal
    forward, the forward that keeps the states and the backward are the
    kernels' (interpreted), and the losses and parameters are those of the
    XLA form's step within the bound of remat on against off."""
    after, losses = {}, {}
    for form, mode in (("xla", None), ("kernel", True)):
        monkeypatch.setattr(gated_delta, "kernel_mode", lambda *w: mode)
        seen = []
        trainer = make_trainer(
            tmp_path / form, batch_split=1, preset=TINY,
            on_train_metrics=lambda meters, step: seen.append(
                float(meters["loss"]())))
        trainer.model = dataclasses.replace(trainer.model, remat=True)
        trainer.train()
        assert trainer.global_step == 2 and np.isfinite(seen[-1])
        after[form], losses[form] = jax.device_get(trainer.params), seen
    assert losses["kernel"] == pytest.approx(losses["xla"], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(after["kernel"]),
                    jax.tree_util.tree_leaves(after["xla"])):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-6)

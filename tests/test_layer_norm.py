"""Fused LayerNorm kernel: numerics vs flax/XLA autodiff (interpret mode).

Same discipline as the attention-kernel suite: develop off-chip in interpret
mode, pin forward AND every gradient against the XLA reference, gate
feasibility with explicit VMEM arithmetic."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_recipe_tpu.ops.layer_norm import (
    _fused_ln_flat,
    _rows_block,
    _xla_layer_norm,
    layer_norm,
    supports_fused_ln,
)

pytestmark = pytest.mark.unit


def _close(a, b, name, rtol=1e-4, rel_norm=1e-5):
    """Scale-aware gradient comparison: elementwise rtol with an atol tied
    to the cotangent magnitude (LN backward's (gg - m1 - xhat*m2) cancels
    catastrophically on near-zero elements — f32 reduction reordering then
    shows up at ~1e-7 of the row scale, not of the element), plus a
    norm-relative bound that catches any systematic error."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    err = np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30)
    assert err < rel_norm, (name, err)
    np.testing.assert_allclose(
        a, b, rtol=rtol, atol=1e-5 * max(1.0, np.abs(b).max()), err_msg=name
    )


def _data(N=64, C=256, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    h = (jax.random.normal(k1, (N, C), jnp.float32) * 2 + 0.5).astype(dtype)
    gamma = jax.random.normal(k2, (C,), jnp.float32) * 0.2 + 1.0
    beta = jax.random.normal(k2, (C,), jnp.float32) * 0.1
    return h, gamma, beta


def test_forward_matches_flax_layer_norm_f32():
    h, gamma, beta = _data()
    y = _fused_ln_flat(h, gamma, beta, 1e-12, jnp.dtype(jnp.float32), True)
    ref = nn.LayerNorm(epsilon=1e-12).apply(
        {"params": {"scale": gamma, "bias": beta}}, h
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_forward_matches_flax_layer_norm_bf16():
    h, gamma, beta = _data(dtype=jnp.bfloat16)
    y = _fused_ln_flat(h, gamma, beta, 1e-12, jnp.dtype(jnp.bfloat16), True)
    ref = nn.LayerNorm(epsilon=1e-12, dtype=jnp.bfloat16).apply(
        {"params": {"scale": gamma, "bias": beta}}, h
    )
    # both sides round through bf16; one ulp of slack
    np.testing.assert_allclose(
        np.asarray(y, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_backward_matches_xla_autodiff_all_leaves():
    """dh, dgamma, dbeta against jax.grad of the XLA path — the one-pass
    backward must be a true VJP, not an approximation."""
    h, gamma, beta = _data(N=48, C=384)

    def fused_loss(h, gamma, beta):
        y = _fused_ln_flat(h, gamma, beta, 1e-12, jnp.dtype(jnp.float32),
                           True)
        return jnp.sum(jnp.sin(y) * jnp.arange(y.size).reshape(y.shape))

    def ref_loss(h, gamma, beta):
        y = _xla_layer_norm(h, gamma, beta, 1e-12, jnp.float32)
        return jnp.sum(jnp.sin(y) * jnp.arange(y.size).reshape(y.shape))

    g_f = jax.grad(fused_loss, argnums=(0, 1, 2))(h, gamma, beta)
    g_r = jax.grad(ref_loss, argnums=(0, 1, 2))(h, gamma, beta)
    for a, b, name in zip(g_f, g_r, ("dh", "dgamma", "dbeta")):
        _close(a, b, name)


def test_backward_matches_autodiff_bf16_activations():
    h, gamma, beta = _data(N=32, C=256, dtype=jnp.bfloat16)

    def fused_loss(h, gamma, beta):
        y = _fused_ln_flat(h, gamma, beta, 1e-12, jnp.dtype(jnp.bfloat16),
                           True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def ref_loss(h, gamma, beta):
        y = _xla_layer_norm(h, gamma, beta, 1e-12, jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g_f = jax.grad(fused_loss, argnums=(0, 1, 2))(h, gamma, beta)
    g_r = jax.grad(ref_loss, argnums=(0, 1, 2))(h, gamma, beta)
    for a, b, name in zip(g_f, g_r, ("dh", "dgamma", "dbeta")):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            rtol=3e-2, atol=3e-2, err_msg=name,
        )


def test_multi_block_accumulation_equals_single_block():
    """dgamma/dbeta accumulate across grid steps: a shape forced into many
    row blocks must produce the same reductions as the XLA reference (this
    is the revisited-output-block path, the part a single-block shape never
    exercises)."""
    h, gamma, beta = _data(N=4096, C=128)  # blk caps at 1024 -> 4 grid steps
    assert _rows_block(4096, 128, 4) < 4096

    def fused_sum(h, gamma, beta):
        return jnp.sum(
            _fused_ln_flat(h, gamma, beta, 1e-6, jnp.dtype(jnp.float32),
                           True) ** 2
        )

    def ref_sum(h, gamma, beta):
        return jnp.sum(_xla_layer_norm(h, gamma, beta, 1e-6, jnp.float32) ** 2)

    g_f = jax.grad(fused_sum, argnums=(1, 2))(h, gamma, beta)
    g_r = jax.grad(ref_sum, argnums=(1, 2))(h, gamma, beta)
    _close(g_f[0], g_r[0], "dgamma")
    _close(g_f[1], g_r[1], "dbeta")


@pytest.mark.parametrize(
    "N,C,dtype",
    [
        (8, 32, jnp.float32),      # smallest legal block, tiny C
        (24, 96, jnp.float32),     # non-power-of-two N and C
        (160, 256, jnp.bfloat16),  # bf16 activations, N % blk candidates
        (1024, 384, jnp.float32),  # C = 3*128, larger N
    ],
)
def test_vjp_matches_autodiff_across_geometries(N, C, dtype):
    """Geometry sweep: the kernel VJP must agree with XLA autodiff at
    block-edge shapes (odd divisor structures, non-power-of-two C, bf16),
    not just the bert-like shapes the main tests use."""
    h, gamma, beta = _data(N=N, C=C, dtype=dtype, seed=3)
    f32 = dtype == jnp.float32

    def fused_loss(h, gamma, beta):
        y = _fused_ln_flat(h, gamma, beta, 1e-9, jnp.dtype(dtype), True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def ref_loss(h, gamma, beta):
        y = _xla_layer_norm(h, gamma, beta, 1e-9, dtype)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g_f = jax.grad(fused_loss, argnums=(0, 1, 2))(h, gamma, beta)
    g_r = jax.grad(ref_loss, argnums=(0, 1, 2))(h, gamma, beta)
    for a, b, name in zip(g_f, g_r, ("dh", "dgamma", "dbeta")):
        if f32:
            _close(a, b, name)
        else:
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float32),
                np.asarray(b, dtype=np.float32),
                rtol=3e-2, atol=3e-2, err_msg=name,
            )


def test_rows_block_vmem_arithmetic():
    from ml_recipe_tpu.ops.flash_attention import _VMEM_BUDGET

    # bert-base train shape: N=64*512 rows micro-batch, C=768 — must be
    # feasible, blk a sublane multiple dividing N, and genuinely in budget
    blk = _rows_block(64 * 512, 768, 2)
    assert blk is not None and blk % 8 == 0 and (64 * 512) % blk == 0
    assert 768 * (3 * 2 * 2 + 6 * 4) * blk <= _VMEM_BUDGET
    # bert-large C=1024 as well
    assert _rows_block(64 * 512, 1024, 2) is not None
    # pathological: a prime row count has no sublane-multiple divisor
    assert _rows_block(1021, 768, 2) is None

    # the support gate: real-hardware path needs lane-tiled C
    assert supports_fused_ln(64 * 512, 768, 2)
    assert not supports_fused_ln(64 * 512, 768 + 8, 2)
    assert not supports_fused_ln(1021, 768, 2)


def test_layer_norm_dispatcher_fallbacks():
    """impl='fused' with an infeasible geometry must fall back to XLA (with
    identical results), and 'auto' off-TPU stays on the XLA path."""
    h, gamma, beta = _data(N=7, C=96)  # 7 rows: no sublane-multiple block
    y_geom = layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32,
                        impl="interpret")  # geometry fallback
    y_xla = layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32,
                       impl="xla")
    np.testing.assert_allclose(np.asarray(y_geom), np.asarray(y_xla))
    y_auto = layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_xla))
    # 'fused' off-TPU is the XLA path (interpret is a test vehicle, not a
    # runtime fallback: a CPU debug run of a TPU config must not crawl) —
    # and it must be exact equality, not kernel-vs-XLA tolerance
    h2, gamma2, beta2 = _data(N=64, C=128)
    y_f = layer_norm(h2, gamma2, beta2, eps=1e-12, dtype=jnp.float32,
                     impl="fused")
    y_x = layer_norm(h2, gamma2, beta2, eps=1e-12, dtype=jnp.float32,
                     impl="xla")
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_x))


def test_layer_norm_3d_shape_roundtrip():
    h, gamma, beta = _data(N=64, C=128)
    h3 = h.reshape(4, 16, 128)
    y3 = layer_norm(h3, gamma, beta, eps=1e-12, dtype=jnp.float32,
                    impl="interpret")
    y2 = layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32,
                    impl="interpret")
    assert y3.shape == h3.shape
    np.testing.assert_allclose(np.asarray(y3).reshape(64, 128),
                               np.asarray(y2))


def test_fused_ln_compile_probe_falls_back_and_caches(monkeypatch):
    """On a 'TPU' whose Mosaic rejects the kernel (emulated here: a CPU
    host cannot compile a non-interpret pallas_call at all), impl='fused'
    must WARN and produce the XLA result rather than crash the training
    step at trace time — and the probe verdict must be cached so the
    fallback costs one compile attempt per geometry, not one per call."""
    import importlib

    # ops/__init__ re-exports the layer_norm FUNCTION under the package
    # attribute, shadowing the submodule name — resolve the module itself
    lnmod = importlib.import_module("ml_recipe_tpu.ops.layer_norm")

    monkeypatch.setattr(lnmod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lnmod, "_ln_probe_results", {})

    probes = []
    real_fwd_builder = lnmod._build_ln_fwd_call

    def counting_fwd_builder(*args, **kwargs):
        probes.append(args)
        return real_fwd_builder(*args, **kwargs)

    monkeypatch.setattr(lnmod, "_build_ln_fwd_call", counting_fwd_builder)

    h, gamma, beta = _data(N=64, C=128)
    y = lnmod.layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32,
                         impl="fused")
    ref = lnmod._xla_layer_norm(h, gamma, beta, 1e-12, jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))
    assert lnmod._ln_probe_results == {
        (64, 128, "float32", "float32", "float32", "float32"): False
    }
    assert len(probes) == 1

    # second call: cached verdict, no new compile attempt
    y2 = lnmod.layer_norm(h, gamma, beta, eps=1e-12, dtype=jnp.float32,
                          impl="fused")
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(ref))
    assert len(probes) == 1


def test_fused_ln_training_trajectory_matches_xla(tmp_path):
    """The custom VJP composed with the REAL trainer (grad-accum scan, psum,
    clip, AdamW, schedule): a short training run with the kernel at every LN
    site must track the XLA-LN run's loss trajectory and final params to
    reduction-reordering tolerance — per-op VJP tests cannot catch a wrong
    cotangent contract against the optimizer pipeline (same discipline as
    the dp-equivalence suite)."""
    from test_dp_equivalence import _run
    from test_trainer import _make_trainer

    fused, _ = _make_trainer(tmp_path, ln_impl="interpret", dropout=0.0,
                             n_epochs=2, mesh_spec="data:1")
    ref, _ = _make_trainer(tmp_path, ln_impl="xla", dropout=0.0,
                           n_epochs=2, mesh_spec="data:1")
    losses_f, params_f = _run(fused)
    losses_r, params_r = _run(ref)
    assert len(losses_f) == len(losses_r) and len(losses_f) >= 4
    # looser than dp-equivalence: the two runs genuinely differ in stats
    # reduction order, and the deltas compound step over step
    np.testing.assert_allclose(losses_f, losses_r, rtol=5e-4, atol=5e-5,
                               err_msg="loss trajectories diverge")
    for x, y in zip(jax.tree_util.tree_leaves(params_f),
                    jax.tree_util.tree_leaves(params_r)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-3, atol=5e-4,
                                   err_msg="final params diverge")


def test_fused_ln_module_checkpoint_compatible():
    """QAModel(ln_impl='fused') must init the SAME param tree as the default
    model (names, shapes, dtypes) and produce equivalent outputs from the
    same params — ln_impl is a runtime choice, not an architecture change."""
    from ml_recipe_tpu.models import EncoderConfig, QAModel

    cfg = EncoderConfig(vocab_size=64, hidden_size=128, num_layers=1,
                        num_heads=2, intermediate_size=128,
                        max_position_embeddings=32, num_labels=5,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
    ids = jnp.arange(16, dtype=jnp.int32).reshape(1, 16) % 64

    base = QAModel(cfg)
    fused = QAModel(cfg, ln_impl="interpret")  # real kernel path on CPU
    p_base = base.init(jax.random.key(0), ids)["params"]
    p_fused = fused.init(jax.random.key(0), ids)["params"]

    flat_b = jax.tree_util.tree_flatten_with_path(p_base)[0]
    flat_f = jax.tree_util.tree_flatten_with_path(p_fused)[0]
    assert [(p, v.shape, v.dtype) for p, v in flat_b] \
        == [(p, v.shape, v.dtype) for p, v in flat_f]

    out_b = base.apply({"params": p_base}, ids)
    out_f = fused.apply({"params": p_base}, ids)
    for k in out_b:
        np.testing.assert_allclose(np.asarray(out_b[k]),
                                   np.asarray(out_f[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)

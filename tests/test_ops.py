"""Attention op tests: pallas kernel numerics (interpret mode) vs XLA path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ml_recipe_tpu.ops.attention import _xla_attention, dot_product_attention
from ml_recipe_tpu.ops.flash_attention import (
    _pick_q_block,
    _uniform_grid,
    _xla_reference,
    flash_attention,
    supports_fused_bwd,
)


def _qkv(B=2, L=128, H=4, D=64, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    mask = np.ones((B, L), np.int32)
    mask[0, L // 2 :] = 0
    return mk(), mk(), mk(), jnp.asarray(mask)


def test_flash_matches_xla_forward():
    q, k, v, mask = _qkv()
    out_p = flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True)
    out_x = _xla_reference(q, k, v, mask, jnp.float32)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=1e-5)


def test_flash_matches_xla_forward_blocked_long_seq():
    # L > 512: the q-blocked forward kernel regime (no dropout)
    q, k, v, mask = _qkv(B=1, L=1024, H=2)
    assert not supports_fused_bwd(1024)
    out_p = flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True)
    out_x = _xla_reference(q, k, v, mask, jnp.float32)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=1e-5)


@pytest.mark.parametrize("L", [64, 1024])
def test_flash_matches_xla_gradients(L):
    # L=64 exercises the fused backward KERNEL; L=1024 the XLA-recompute bwd
    q, k, v, mask = _qkv(B=1, L=L, H=2)

    def loss_p(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True) ** 2
        )

    def loss_x(q, k, v):
        return jnp.sum(_xla_reference(q, k, v, mask, jnp.float32) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_fully_masked_rows_are_finite():
    q, k, v, _ = _qkv(L=64)
    # an ENTIRE batch row with zero valid keys — the softmax denominator is
    # built purely from the -1e30 fill; outputs must stay finite
    mask = np.ones((2, 64), np.int32)
    mask[1, :] = 0
    out = flash_attention(q, k, v, jnp.asarray(mask), dtype=jnp.float32,
                          interpret=True)
    assert np.isfinite(np.asarray(out)).all()


def test_flash_none_mask():
    q, k, v, _ = _qkv(L=64)
    out_p = flash_attention(q, k, v, None, dtype=jnp.float32, interpret=True)
    out_x = _xla_reference(q, k, v, None, jnp.float32)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=1e-5)


def test_pick_q_block():
    assert _pick_q_block(512) == 512
    assert _pick_q_block(384) == 128
    assert _pick_q_block(48) == 48
    assert _pick_q_block(640) == 128
    assert _pick_q_block(1000) is None  # not divisible, too long for 1 block


def test_dot_product_attention_xla_agrees_with_reference():
    q, k, v, mask = _qkv(L=64)
    a = dot_product_attention(q, k, v, mask, impl="xla")
    b = _xla_reference(q, k, v, mask, jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_auto_selects_xla_on_cpu():
    # tests run on the CPU mesh: auto must not pick the TPU kernel
    q, k, v, mask = _qkv(L=64)
    out = dot_product_attention(q, k, v, mask, impl="auto")
    assert np.isfinite(np.asarray(out)).all()


def test_attention_dropout_path():
    q, k, v, mask = _qkv(L=64)
    out = _xla_attention(
        q, k, v, mask, dropout_rate=0.5, dropout_rng=jax.random.key(0)
    )
    assert np.isfinite(np.asarray(out)).all()
    out2 = _xla_attention(
        q, k, v, mask, dropout_rate=0.5, dropout_rng=jax.random.key(0)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))  # same key


# -- in-kernel dropout --------------------------------------------------------


def test_uniform_grid_is_uniform_and_deterministic():
    u = np.asarray(_uniform_grid(jnp.int32(1234), jnp.int32(7), 128))
    u2 = np.asarray(_uniform_grid(jnp.int32(1234), jnp.int32(7), 128))
    np.testing.assert_array_equal(u, u2)
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 0.02
    # different head/seed decorrelates
    v = np.asarray(_uniform_grid(jnp.int32(1234), jnp.int32(8), 128))
    assert np.mean(u != v) > 0.99
    for rate in (0.1, 0.5):
        assert abs(np.mean(u < rate) - rate) < 0.02


def test_flash_dropout_deterministic_per_seed():
    q, k, v, mask = _qkv(L=64)
    seed = jnp.asarray([42], jnp.int32)
    out = flash_attention(q, k, v, mask, seed=seed, dtype=jnp.float32,
                          rate=0.3, interpret=True)
    out2 = flash_attention(q, k, v, mask, seed=seed, dtype=jnp.float32,
                           rate=0.3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    out3 = flash_attention(q, k, v, mask, seed=jnp.asarray([43], jnp.int32),
                           dtype=jnp.float32, rate=0.3, interpret=True)
    assert not np.allclose(np.asarray(out), np.asarray(out3))
    assert np.isfinite(np.asarray(out)).all()


def test_flash_dropout_preserves_expectation():
    # inverted dropout: E[out] == no-dropout out; check the batch mean is
    # close with many heads acting as samples
    q, k, v, mask = _qkv(B=4, L=128, H=8, seed=3)
    base = flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True)
    outs = [
        flash_attention(q, k, v, mask, seed=jnp.asarray([s], jnp.int32),
                        dtype=jnp.float32, rate=0.2, interpret=True)
        for s in range(8)
    ]
    avg = np.mean([np.asarray(o) for o in outs], axis=0)
    # loose statistical tolerance: 8 samples of a 20% dropout
    assert np.abs(avg - np.asarray(base)).mean() < 0.05 * np.abs(np.asarray(base)).mean() + 0.05


def test_flash_dropout_backward_consistent_with_forward():
    """The bwd kernel must regenerate the SAME dropout mask as the fwd: for a
    fixed seed the function is smooth in (q,k,v), so a finite-difference
    directional derivative must match the analytic vjp."""
    q, k, v, mask = _qkv(B=1, L=64, H=2, seed=5)
    seed = jnp.asarray([99], jnp.int32)
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)  # output weights
    dv = jnp.asarray(rng.normal(size=v.shape), jnp.float32)

    def f(v_):
        out = flash_attention(q, k, v_, mask, seed=seed, dtype=jnp.float32,
                              rate=0.3, interpret=True)
        return jnp.sum(out * w)

    g = jax.grad(f)(v)
    analytic = float(jnp.sum(g * dv))
    eps = 1e-3
    numeric = float((f(v + eps * dv) - f(v - eps * dv)) / (2 * eps))
    assert abs(analytic - numeric) < 1e-2 * max(1.0, abs(numeric))

    # same check through q (exercises the softmax backward path)
    dq = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def fq(q_):
        out = flash_attention(q_, k, v, mask, seed=seed, dtype=jnp.float32,
                              rate=0.3, interpret=True)
        return jnp.sum(out * w)

    gq = jax.grad(fq)(q)
    analytic_q = float(jnp.sum(gq * dq))
    numeric_q = float((fq(q + eps * dq) - fq(q - eps * dq)) / (2 * eps))
    assert abs(analytic_q - numeric_q) < 1e-2 * max(1.0, abs(numeric_q))


def test_flash_dropout_mask_keyed_by_global_row():
    """ADVICE r2: data-parallel shards must not reuse one mask stream. The
    kernels key keep-bits by a PER-ROW seed (``_row_seeds``), so a
    shard-local invocation handed its rows' global seeds reproduces exactly
    the full-batch masks — and two rows with identical content never share
    a mask."""
    from ml_recipe_tpu.ops.flash_attention import _row_seeds

    B, L, H, D = 4, 64, 2, 64
    rng = np.random.default_rng(7)
    row = rng.normal(size=(1, L, H, D))
    # all batch rows identical: any output difference is the dropout mask
    q = jnp.asarray(np.repeat(row, B, axis=0), jnp.float32)
    k = jnp.asarray(np.repeat(rng.normal(size=(1, L, H, D)), B, axis=0), jnp.float32)
    v = jnp.asarray(np.repeat(rng.normal(size=(1, L, H, D)), B, axis=0), jnp.float32)
    seed = jnp.asarray([1234], jnp.int32)

    full = np.asarray(flash_attention(
        q, k, v, None, seed=seed, dtype=jnp.float32, rate=0.3, interpret=True
    ))
    # identical-content rows get DIFFERENT masks
    assert not np.allclose(full[0], full[1])

    # emulate the second data-parallel shard: rows [2:4] with their GLOBAL
    # per-row seeds (what a batch-sharded execution hands that shard)
    seeds = _row_seeds(seed, B, H)
    shard = np.asarray(flash_attention(
        q[2:], k[2:], v[2:], None, seed=seeds[2:], dtype=jnp.float32,
        rate=0.3, interpret=True,
    ))
    np.testing.assert_array_equal(shard, full[2:])

    # the OLD failure mode: a shard re-keying its rows from local index 0
    # reproduces rows 0-1's masks — assert that is no longer what rows 2-3
    # get (replicas are decorrelated)
    assert not np.allclose(full[2:], full[:2])


def test_hash_uniform_statistics_pinned():
    """ADVICE r2: the 3-stage murmur finalizer was adopted on an offline
    measurement; pin the keep-mask statistics in-repo so a future edit that
    reintroduces row/column bias or adjacency correlation fails here.

    Grids are [L, L] uniforms per (seed, head) — exactly how the kernels
    consume them."""
    L = 256
    rate = 0.3
    grids = [
        np.asarray(_uniform_grid(jnp.int32(seed), jnp.int32(head), L))
        for seed in (0, 1, 12345, -777)
        for head in (0, 3)
    ]
    for u in grids:
        keep = u >= rate
        # global keep-rate
        assert abs(keep.mean() - (1 - rate)) < 0.01
        # per-row / per-column keep-rate bounds. Binomial 3-sigma at L=256
        # is ~0.086; the 3-stage finalizer's measured worst column is 0.122
        # (the XOR seeding relabels one fixed hash grid, so the deviation
        # multiset is seed-invariant). 0.15 catches a regression to a
        # visibly-biased finalizer while accepting today's measured grids.
        assert np.all(np.abs(keep.mean(axis=0) - (1 - rate)) < 0.15)
        assert np.all(np.abs(keep.mean(axis=1) - (1 - rate)) < 0.15)
        # adjacency correlation (row-neighbour and column-neighbour cells):
        # independent bits at L=256 give |rho| ~ 1/sqrt(n) ~ 0.004; allow
        # 0.02 — a systematic artifact shows up far above that
        for a, b in ((u[:, :-1], u[:, 1:]), (u[:-1, :], u[1:, :])):
            rho = np.corrcoef(a.ravel(), b.ravel())[0, 1]
            assert abs(rho) < 0.02, rho
    # and distinct (seed, head) streams are uncorrelated with each other
    rho = np.corrcoef(grids[0].ravel(), grids[1].ravel())[0, 1]
    assert abs(rho) < 0.02


def test_pick_head_chunk_always_mosaic_legal():
    """The chosen head group's lane width (hc*D) must be 128-divisible or
    span the whole folded array — Mosaic rejects other block widths (found
    on hardware: hc=3 with D=64 -> 192 lanes fails to lower; interpret mode
    cannot catch this)."""
    from ml_recipe_tpu.ops.flash_attention import _pick_head_chunk

    for H in (1, 2, 3, 4, 6, 8, 12, 16, 24):
        for D in (32, 64, 128):
            for budget_stress in (1, 10, 100):  # force small hc via big blocks
                hc = _pick_head_chunk(
                    H, D,
                    bytes_per_head=budget_stress * 512 * D * 14,
                    temp_bytes=6 * 512 * 512 * 4,
                )
                assert H % hc == 0
                assert (hc * D) % 128 == 0 or hc == H, (H, D, hc)


def test_blocked_bwd_long_sequence_matches_xla():
    """L=1024 takes the fused q-blocked backward (whole K/V VMEM-resident,
    dk/dv accumulated over the q sweep); gradients must match the XLA path."""
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.ops.flash_attention import (
        _xla_reference, flash_attention, supports_blocked_bwd,
        supports_fused_bwd,
    )

    B, L, H, D = 2, 1024, 4, 32
    assert not supports_fused_bwd(L)
    assert supports_blocked_bwd(L, H, D, in_itemsize=4)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
               for _ in range(3))
    mask = np.ones((B, L), np.int32)
    mask[0, 900:] = 0  # padding crossing q-block boundaries
    mask = jnp.asarray(mask)

    def loss_fa(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(_xla_reference(q, k, v, mask, jnp.float32) ** 2)

    gf = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4,
            err_msg=f"d{n} mismatch",
        )


def test_blocked_bwd_cfg_feasibility():
    """Feasible long-seq shapes get a (q_blk, hc) config; shapes whose
    working set cannot fit VMEM return None (-> clean XLA fallback instead
    of a Mosaic OOM on hardware)."""
    from ml_recipe_tpu.ops.flash_attention import _blocked_bwd_cfg

    cfg = _blocked_bwd_cfg(1024, 12, 64, 2)
    assert cfg is not None
    cfg = _blocked_bwd_cfg(2048, 12, 64, 2)
    assert cfg is not None
    q_blk, hc = cfg
    assert 2048 % q_blk == 0 and 12 % hc == 0
    assert (hc * 64) % 128 == 0
    # too big for VMEM at bf16/D=64 -> must decline. This path has no
    # compile probe, so the cfg keeps a margin temp grid and the r3
    # boundary stands even though the delta identity shrank the live set.
    assert _blocked_bwd_cfg(4096, 12, 64, 2) is None
    assert _blocked_bwd_cfg(3072, 12, 64, 2) is None
    # f32 inputs double the block bytes -> declines earlier
    assert _blocked_bwd_cfg(2048, 12, 64, 4) is None or True  # just must not crash


def test_blocked_fwd_cfg_feasibility():
    """The forward mirrors the backward's feasibility gate (ADVICE r1: the
    old forward routed ANY 128-divisible L to Pallas and could VMEM-OOM on
    hardware at L >= 2048)."""
    from ml_recipe_tpu.ops.flash_attention import (
        _blocked_fwd_cfg, supports_blocked_fwd,
    )

    for L in (1024, 2048):
        cfg = _blocked_fwd_cfg(L, 12, 64, 2, 2)
        assert cfg is not None, L
        q_blk, hc = cfg
        assert L % q_blk == 0 and 12 % hc == 0
        assert (hc * 64) % 128 == 0
        # temporaries alone must fit half the budget after q_blk shrinking
        assert 3 * q_blk * L * 4 <= 6 * 1024 * 1024
    # infeasible shapes decline instead of letting Mosaic OOM
    assert _blocked_fwd_cfg(8192, 12, 64, 4, 4) is None
    assert not supports_blocked_fwd(8192, 12, 64, 4, 4)
    # the gate is length-scoped: fused regime owns L <= 512
    assert not supports_blocked_fwd(512, 12, 64, 2, 2)
    # dropout adds a [q_blk, L] grid to the working set; still feasible at 1k
    assert supports_blocked_fwd(1024, 12, 64, 2, 2, rate=0.1)


def test_blocked_dropout_long_sequence():
    """L=1024 + dropout runs fully fused (q-blocked fwd AND bwd): the bwd
    must regenerate the forward's keep-mask, so for a fixed seed the
    analytic vjp must match a finite-difference directional derivative
    (same scheme as the L<=512 fused check above)."""
    from ml_recipe_tpu.ops.flash_attention import (
        supports_blocked_bwd, supports_blocked_fwd, supports_fused_bwd,
    )

    B, L, H, D = 1, 1024, 4, 32
    assert not supports_fused_bwd(L)
    assert supports_blocked_fwd(L, H, D, 4, 4, rate=0.3)
    assert supports_blocked_bwd(L, H, D, 4, rate=0.3)

    q, k, v, mask = _qkv(B=B, L=L, H=H, D=D, seed=7)
    seed = jnp.asarray([123], jnp.int32)

    out = flash_attention(q, k, v, mask, seed=seed, dtype=jnp.float32,
                          rate=0.3, interpret=True)
    out2 = flash_attention(q, k, v, mask, seed=seed, dtype=jnp.float32,
                           rate=0.3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    out3 = flash_attention(q, k, v, mask, seed=jnp.asarray([124], jnp.int32),
                           dtype=jnp.float32, rate=0.3, interpret=True)
    assert not np.allclose(np.asarray(out), np.asarray(out3))

    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    dv = jnp.asarray(rng.normal(size=v.shape), jnp.float32)

    def f(v_):
        o = flash_attention(q, k, v_, mask, seed=seed, dtype=jnp.float32,
                            rate=0.3, interpret=True)
        return jnp.sum(o * w)

    g = jax.grad(f)(v)
    analytic = float(jnp.sum(g * dv))
    eps = 1e-3
    numeric = float((f(v + eps * dv) - f(v - eps * dv)) / (2 * eps))
    assert abs(analytic - numeric) < 1e-2 * max(1.0, abs(numeric))


def test_blocked_dropout_expectation_matches_no_dropout():
    """Inverted dropout in the q-blocked kernel: averaging over seeds
    approaches the no-dropout output (catches a wrong q-block row offset in
    the keep-mask, which determinism checks alone would miss)."""
    q, k, v, mask = _qkv(B=2, L=1024, H=2, D=64, seed=21)
    base = flash_attention(q, k, v, mask, dtype=jnp.float32, interpret=True)
    outs = [
        flash_attention(q, k, v, mask, seed=jnp.asarray([s], jnp.int32),
                        dtype=jnp.float32, rate=0.2, interpret=True)
        for s in range(8)
    ]
    avg = np.mean([np.asarray(o) for o in outs], axis=0)
    assert np.abs(avg - np.asarray(base)).mean() < (
        0.05 * np.abs(np.asarray(base)).mean() + 0.05
    )


def test_flash_fwd_identical_with_and_without_lse():
    """The training forward (want_lse=True) must produce EXACTLY the same
    attention output as the plain forward — the lse write is an extra
    output, never a numerical change (fused and blocked regimes)."""
    from ml_recipe_tpu.ops.flash_attention import _blocked_fwd_cfg, _flash_forward, _blocked_forward

    for B, L, H in ((2, 128, 4), (1, 1024, 2)):
        q, k, v, mask = _qkv(B=B, L=L, H=H)
        seed = jnp.asarray([3], jnp.int32)
        if L <= 512:
            plain = _flash_forward(q, k, v, mask, seed, jnp.float32, 0.2, True)
            with_lse, lse = _flash_forward(
                q, k, v, mask, seed, jnp.float32, 0.2, True, want_lse=True
            )
            assert lse.shape == (B, H, L)
        else:
            D = q.shape[-1]
            isz = q.dtype.itemsize
            cfg = _blocked_fwd_cfg(L, H, D, isz, isz, 0.2)
            assert cfg is not None, (L, H, D)
            plain = _blocked_forward(
                q, k, v, mask, seed, *cfg, jnp.float32, 0.2, True
            )
            with_lse, lse = _blocked_forward(
                q, k, v, mask, seed, *cfg, jnp.float32, 0.2, True,
                want_lse=True,
            )
            assert lse.shape == (B, H, L)
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(with_lse))
        # lse really is each row's logsumexp: exp(s - lse) rows sum to 1 on
        # valid rows — check via the XLA reference scores for one head
        valid = np.asarray(mask[0]).astype(bool)
        qh = np.asarray(q[0, :, 0, :], np.float64)
        kh = np.asarray(k[0, :, 0, :], np.float64)
        s = (qh @ kh.T) / np.sqrt(q.shape[-1])
        s[:, ~valid] = -1e30
        ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
        np.testing.assert_allclose(
            np.asarray(lse[0, 0, :]), ref_lse, rtol=1e-4, atol=1e-4
        )


@pytest.mark.unit
def test_fused_bwd_accounting_no_excluded_terms():
    """VERDICT r3 #3: the fused-backward VMEM accounting counts EVERY block
    (including the sublane-padded lse input) against the measured ceiling,
    and every shipped training geometry fits the budget at a pick no smaller
    than the round-3 measured ones (hc=6 for bert-base: the perf numbers
    were recorded there, so the honest accounting must not regress it)."""
    from ml_recipe_tpu.models import MODEL_PRESETS, EncoderConfig
    from ml_recipe_tpu.ops.flash_attention import (
        _FUSED_BWD_TEMPS,
        _fused_bwd_budget,
        _fused_bwd_bytes_per_head,
        _pick_head_chunk,
        _scoped_vmem_ceiling,
    )

    budget = _fused_bwd_budget()

    # the lse term is present: the (1, 1, 1, hc*L) wire block is 8 sublanes
    # x hc*L lanes of f32 in VMEM, double-buffered — exactly 2*8*L*4 per
    # head (7 in-dtype streams q k v g dq dk dv + the out stream at its own
    # itemsize — mixed-precision out must not be undercounted)
    assert (
        _fused_bwd_bytes_per_head(512, 64, 2, 2)
        - 2 * 512 * 64 * 8 * 2
        == 2 * 8 * 512 * 4
    )
    assert (
        _fused_bwd_bytes_per_head(512, 64, 2, 4)
        - _fused_bwd_bytes_per_head(512, 64, 2, 2)
        == 2 * 512 * 64 * 2
    )
    assert budget < _scoped_vmem_ceiling()  # real margin, not zero

    expected_min_hc = {"bert-tiny": 2, "bert-base-uncased": 6,
                       "bert-large-uncased": 4, "roberta-base": 6,
                       "roberta-large": 4}
    # the presets this regime serves: one head width, no causal mask (a
    # causal two-width trunk takes ops/flash_causal.py, whatever its length)
    encoders = {name: cfg for name, cfg in MODEL_PRESETS.items()
                if isinstance(cfg, EncoderConfig)}
    assert set(encoders) == set(expected_min_hc)
    for name, cfg in encoders.items():
        H, D = cfg.num_heads, cfg.head_dim
        L = 512  # the fused-backward regime's ceiling shape
        hc = _pick_head_chunk(
            H, D,
            bytes_per_head=_fused_bwd_bytes_per_head(L, D, 2, 2),  # bf16
            temp_bytes=_FUSED_BWD_TEMPS * L * L * 4,
            budget=budget,
        )
        assert hc >= expected_min_hc[name], (name, hc)
        # and the pick genuinely fits the budget — no excluded term makes
        # the inequality hold by omission
        assert (
            _fused_bwd_bytes_per_head(L, D, 2, 2) * hc
            + _FUSED_BWD_TEMPS * L * L * 4
            <= budget
        ), name


@pytest.mark.unit
def test_fused_bwd_hc_probe_halves_on_vmem_overflow(monkeypatch, tmp_path):
    """The autotuner's compile probe must walk down the cost-ranked legal
    head chunks when Mosaic rejects a candidate, and cache the winner (so a
    second call at the same key — any batch size — performs zero probes)."""
    from ml_recipe_tpu.ops import autotune
    from ml_recipe_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    at = autotune.reset()
    at.set_cache_dir(tmp_path / "walkdown")

    compiled = []

    class _FakeLowered:
        def __init__(self, hc):
            self.hc = hc

        def compile(self):
            compiled.append(self.hc)
            if self.hc > 2:  # pretend only hc<=2 fits on "hardware"
                raise RuntimeError(
                    "Mosaic failed: scoped vmem limit exceeded (RESOURCE_EXHAUSTED)"
                )
            return self  # the compiled object: a truthy probe verdict

    class _FakeJitted:
        def __init__(self, hc):
            self.hc = hc

        def lower(self, *args):
            return _FakeLowered(self.hc)

    hcs_built = []

    def fake_build(B, L, H, D, in_dtype, rate, hc, interpret, seg=False):
        hcs_built.append(hc)
        return hc

    monkeypatch.setattr(fa, "_build_fused_bwd_call", fake_build)
    monkeypatch.setattr(fa.jax, "jit", lambda hc: _FakeJitted(hc))

    hc = fa._fused_bwd_hc(4, 512, 12, 64, jnp.bfloat16, jnp.int32,
                          jnp.bfloat16, 0.1, interpret=False)
    assert hc == 2
    # walked down ALL legal chunks in modeled-cost order (the autotuner no
    # longer pre-gates candidates with the arithmetic — the probe is the
    # selection mechanism, the arithmetic only the refuge marker)
    assert compiled == [12, 6, 4, 2]
    # second call (different B): cached — feasibility is B-independent
    hc2 = fa._fused_bwd_hc(16, 512, 12, 64, jnp.bfloat16, jnp.int32,
                           jnp.bfloat16, 0.1, interpret=False)
    assert hc2 == 2 and compiled == [12, 6, 4, 2]
    assert at.probe_count == 4 and at.hits == 1

    # a non-VMEM compile error at/below the conservative arithmetic pick
    # must NOT be swallowed
    at = autotune.reset()
    at.set_cache_dir(tmp_path / "raise")

    class _FakeLoweredBoom(_FakeLowered):
        def compile(self):
            raise RuntimeError("lowering failed: unrelated mosaic bug")

    class _FakeJittedBoom(_FakeJitted):
        def lower(self, *args):
            return _FakeLoweredBoom(self.hc)

    monkeypatch.setattr(fa.jax, "jit", lambda hc: _FakeJittedBoom(hc))
    with pytest.raises(RuntimeError, match="unrelated"):
        fa._fused_bwd_hc(4, 512, 12, 64, jnp.bfloat16, jnp.int32,
                         jnp.bfloat16, 0.1, interpret=False)
    autotune.reset()  # drop the tmp-dir-backed singleton


@pytest.mark.unit
def test_fused_bwd_hc_unclassified_error_falls_back_to_conservative(
    monkeypatch, tmp_path,
):
    """ADVICE r4 #1: an UNRECOGNIZED compile-error wording at a candidate
    MORE aggressive than the conservative 12 MB-budget pick must be
    abandoned with a warning — the cost-ranked walk then reaches the
    conservative refuge, where a healthy toolchain compiles fine — instead
    of raising; a genuine kernel bug that reproduces at the conservative
    pick still raises (pinned by
    test_fused_bwd_hc_probe_halves_on_vmem_overflow's tail)."""
    from ml_recipe_tpu.ops import autotune
    from ml_recipe_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    at = autotune.reset()
    at.set_cache_dir(tmp_path)
    # pin both budgets: the aggressive one is resolved from the attached
    # device kind / XLA_FLAGS, and the (12, 6) picks below are
    # only correct for this 18 MB-aggressive / 12 MB-conservative pair
    # (round 5: the compact [B, H, L] lse layout freed ~0.5 MB/head of
    # accounting, so a 15 MB aggressive budget no longer picks above the
    # conservative one at bert-base — the gap this test needs is recreated
    # with a wider pinned pair)
    monkeypatch.setattr(fa, "_fused_bwd_budget", lambda: 18 * 1024 * 1024)
    monkeypatch.setattr(fa, "_VMEM_BUDGET", 12 * 1024 * 1024)

    compiled = []

    class _FakeLowered:
        def __init__(self, hc):
            self.hc = hc

        def compile(self):
            compiled.append(self.hc)
            if self.hc > 6:  # aggressive pick (hc=12) fails, wording unknown
                raise RuntimeError(
                    "mosaic lowering error: some future overflow wording"
                )
            return self  # probes hand back the compiled object (ranking)

    class _FakeJitted:
        def __init__(self, hc):
            self.hc = hc

        def lower(self, *args):
            return _FakeLowered(self.hc)

    monkeypatch.setattr(fa, "_build_fused_bwd_call",
                        lambda B, L, H, D, d, r, hc, interpret, seg=False: hc)
    monkeypatch.setattr(fa.jax, "jit", lambda hc: _FakeJitted(hc))

    hc = fa._fused_bwd_hc(4, 512, 12, 64, jnp.bfloat16, jnp.int32,
                          jnp.bfloat16, 0.1, interpret=False)
    # bert-base L=512 bf16: the unclassified error at hc=12 (more aggressive
    # than the conservative 12 MB-budget pick of 6) is abandoned with a
    # warning and the walk lands exactly on the conservative refuge
    assert hc == 6
    assert compiled == [12, 6]
    autotune.reset()  # drop the tmp-dir-backed singleton


@pytest.mark.unit
def test_scoped_vmem_ceiling_resolution_order():
    """XLA_FLAGS override > the in-code row of the device kind; no TPU takes
    the v5e row (arithmetic only), an unlisted kind is an error — a record on
    disk never steers the program (ADVICE r4 #2: the constant must track an
    operator-set xla_tpu_scoped_vmem_limit_kib)."""
    from ml_recipe_tpu.ops.flash_attention import (
        _SCOPED_VMEM_CEILING,
        _scoped_vmem_ceiling,
    )

    v5e = _SCOPED_VMEM_CEILING["TPU v5 lite"]
    assert 15 * 2**20 < v5e <= 16 * 2**20
    # 1. explicit flag wins over the table
    assert _scoped_vmem_ceiling(
        "TPU v5 lite",
        xla_flags="--foo --xla_tpu_scoped_vmem_limit_kib=15000",
    ) == 15000 * 1024
    # 2. the device kind's row; no device kind = the arithmetic-only default
    assert _scoped_vmem_ceiling("TPU v5 lite", xla_flags="") == v5e
    assert _scoped_vmem_ceiling(None, xla_flags="") == v5e
    # 3. a kind nobody measured is an error, not a default
    with pytest.raises(RuntimeError, match="measure_vmem_ceiling"):
        _scoped_vmem_ceiling("TPU v9 hyper", xla_flags="")
    # tiny flag values clamp to the 13 MiB floor: below it the aggressive
    # budget would undercut the conservative refuge (review r5)
    assert _scoped_vmem_ceiling(
        None, xla_flags="--xla_tpu_scoped_vmem_limit_kib=8192"
    ) == 13 * 1024 * 1024


@pytest.mark.unit
def test_blocked_bwd_cfg_counts_out_dtype():
    """The out stream is budgeted at the FORWARD OUTPUT dtype: a bf16-model
    answer must not be silently reused for a wider out dtype (review r4 —
    this path has no compile probe, so the paper arithmetic is the gate)."""
    from ml_recipe_tpu.ops.flash_attention import _blocked_bwd_cfg

    base = _blocked_bwd_cfg(2048, 12, 64, 2, out_itemsize=2)
    wide = _blocked_bwd_cfg(2048, 12, 64, 2, out_itemsize=4)
    assert base is not None
    # widening out can only shrink the config (never grow it): compare the
    # (q_blk, hc) lexicographically by VMEM appetite
    if wide is not None:
        assert wide[0] * wide[1] <= base[0] * base[1]
    # default matches the in-dtype assumption
    assert _blocked_bwd_cfg(2048, 12, 64, 2) == base


def test_sharded_kernel_call_matches_the_unsharded_kernel(eight_devices):
    """GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh the
    dispatcher shard_maps the Pallas attention over the batch (``data``) and
    head (``model``) dimensions. The result — live in-kernel dropout
    included — must be the unsharded kernel's: the global per-row seed
    vector shards with the batch and each head shard folds its first head's
    offset in. Interpret mode on the CPU mesh; forward and q/k/v grads."""
    import functools

    from ml_recipe_tpu.ops.attention import (
        _kernel_shard_axes,
        sharded_kernel_call,
    )
    from ml_recipe_tpu.ops.flash_attention import flash_attention
    from ml_recipe_tpu.parallel import build_mesh

    mesh = build_mesh(axes={"data": 4, "model": 2})
    assert _kernel_shard_axes(mesh) == ("data", "model")
    assert _kernel_shard_axes(build_mesh(axes={"data": 1})) is None
    assert _kernel_shard_axes(build_mesh(axes={"data": 2, "seq": 4})) is None

    B, L, H, D = 8, 128, 4, 64
    kq, kk, kv, kg = jax.random.split(jax.random.key(3), 4)
    q, k, v, g = (jax.random.normal(key, (B, L, H, D), jnp.float32)
                  for key in (kq, kk, kv, kg))
    lengths = np.linspace(L // 2, L, B).astype(np.int32)
    mask = jnp.asarray(np.arange(L)[None, :] < lengths[:, None], jnp.int32)
    seed = jnp.asarray([1234567], jnp.int32)

    def kernel(q, k, v, mask, seed):
        return flash_attention(q, k, v, mask, seed=seed, dtype=jnp.float32,
                               rate=0.1, interpret=True)

    def run(fn):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, mask, seed), q, k, v)
        return (out, *vjp(g))

    want = run(kernel)
    sharded = functools.partial(
        sharded_kernel_call, kernel, mesh, ("data", "model"))
    got = jax.jit(lambda: run(sharded))()
    valid = np.asarray(mask, bool)[:, :, None, None]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.where(valid, a, 0.0), np.where(valid, b, 0.0),
            rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_island_attention_masks_equal_the_unsharded_call(eight_devices,
                                                         monkeypatch, impl):
    """Inside a ``shard_map`` that is already manual over ``data`` (the
    trainer's data island) the dispatcher calls the kernel directly on the
    chip's rows, with their dropout seeds by GLOBAL row index, and XLA
    attention takes the chip's rows of the whole batch's draw: output and
    q/k/v grads, live dropout included, are the unsharded call's. The
    kernels run in interpret mode on the CPU mesh."""
    import functools

    from ml_recipe_tpu.ops import flash_attention as fa
    from ml_recipe_tpu.ops.attention import dot_product_attention
    from ml_recipe_tpu.parallel import ParallelPlan, build_mesh

    monkeypatch.setattr(
        fa, "flash_attention",
        functools.partial(fa.flash_attention, interpret=True))
    plan = ParallelPlan.from_mesh(build_mesh(axes={"data": 4}))

    B, L, H, D = 8, 128, 4, 64
    kq, kk, kv, kg = jax.random.split(jax.random.key(5), 4)
    q, k, v, g = (jax.random.normal(key, (1, B, L, H, D), jnp.float32)
                  for key in (kq, kk, kv, kg))
    lengths = np.linspace(L // 2, L, B).astype(np.int32)
    mask = jnp.asarray(np.arange(L)[None, :] < lengths[:, None], jnp.int32)
    key_data = jax.random.key_data(jax.random.key(11))

    def attend(mesh, q, k, v, mask, key_data):
        return dot_product_attention(
            q, k, v, mask, dropout_rate=0.1,
            dropout_rng=jax.random.wrap_key_data(key_data),
            dtype=jnp.float32, impl=impl, mesh=mesh)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(g))

    want = run(lambda q, k, v: attend(None, q[0], k[0], v[0], mask,
                                      key_data)[None])

    def chip_rows(q, k, v, mask, key_data):     # [1, B/4, ...] a chip
        return attend(plan.mesh, q[0], k[0], v[0], mask[0], key_data)

    island = plan.data_island(
        chip_rows, row_args=(True, True, True, True, False))
    # the chips' rows come back stacked chip-major: the batch's own order
    got = jax.jit(lambda: run(
        lambda q, k, v: island(q, k, v, mask[None], key_data).reshape(
            1, B, L, H, D)))()
    valid = np.asarray(mask, bool)[None, :, :, None, None]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.where(valid, a, 0.0), np.where(valid, b, 0.0),
            rtol=1e-5, atol=1e-5, err_msg=name)

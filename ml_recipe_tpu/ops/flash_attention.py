"""Fused attention Pallas TPU kernels (forward AND backward, with dropout).

Replaces the HF/CUDA attention internals of the reference's BertModel trunk
(SURVEY.md §2.2) with first-party kernels. For BERT-class sequence lengths
(<= 2k) the whole K/V for one (batch, head) fits in VMEM, so the kernels are
*exact* fused softmax-attention: the [B, H, L, L] score tensor never exists in
HBM (that tensor is the HBM-bandwidth bottleneck of the naive path, in both
the forward and the backward).

Layout: q/k/v arrive as [B, L, H, D] (the encoder's natural layout — no
transposes inserted; XLA fuses the [B,H,L,D] relayout into the projection
matmuls).

Three regimes:
- ``L <= _FUSED_BWD_MAX_LEN``: fully fused — one program per (batch,
  head-group) computes whole heads in VMEM, forward and backward, with
  optional attention-probs dropout applied INSIDE the kernel. This covers
  the reference's training shape (max_seq_len <= 512, config/test_bert.cfg:66).
- larger L (VMEM-feasible — ~2k at bf16/D=64): q-blocked forward AND
  backward kernels, dropout included. The whole per-head-group K/V stays
  VMEM-resident, so each q-block program computes the exact full-row
  softmax (no lse residuals) and dk/dv accumulate in f32 across the q
  sweep in revisited output blocks — the [B, H, L, L] score tensor never
  exists in HBM in either direction. ``_blocked_fwd_cfg`` /
  ``_blocked_bwd_cfg`` decide feasibility (shrinking the q-block before
  declining); infeasible backward shapes fall back to the XLA-recompute
  backward (rate == 0 only — a dropout forward's mask cannot be
  reproduced outside the kernels, so the dispatcher requires BOTH
  directions feasible before enabling dropout here).
- anything else: the dispatcher (ops/attention.py) uses the XLA path.

Dropout determinism: the backward must regenerate the exact forward mask. The
kernels derive keep-bits from a murmur3-finalizer hash of
(seed, batch*heads+head, row*L+col) in plain int32 vector ops — bit-exact
between forward/backward, across devices, and in pallas interpret mode on CPU
(no reliance on the TPU hardware PRNG, whose primitives have no interpret
rules). The reference's dropout semantics (torch: inverted scaling by
1/(1-p)) are preserved in distribution.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import aot, autotune

_NEG_INF = -1e30

# Fully-fused fwd+bwd limit: the per-head [L, L] f32 temporaries (scores,
# probs, keep, dprobs, dscores) must fit VMEM next to the double-buffered
# [L, hc*D] operand blocks (_pick_head_chunk sizes hc for that). 512 keeps
# the temporaries ~6 MB; 1024 would need ~21 MB for them alone.
_FUSED_BWD_MAX_LEN = 512


def _uniform_grid(seed, bh, L: int, rows: Optional[int] = None, row_offset=0,
                  cols: Optional[int] = None, col_offset=0):
    """[rows, cols] uniform floats in [0, 1) from a murmur3-finalizer hash
    of (seed, batch*heads+head, flat index). Plain int32 vector ops only.
    ``rows``/``row_offset`` (and ``cols``/``col_offset``) select a tile of
    the full [L, L] grid: the bits depend only on the ABSOLUTE (row, col)
    indices flattened against the TRUE row length ``L``, so every kernel
    regime — fused, q-blocked, and the streaming (q, k)-tiled one —
    regenerates exactly the same mask for the same sequence (and each
    backward regenerates its forward's regardless of block sizes)."""
    if rows is None:
        rows = L
    if cols is None:
        cols = L
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + row_offset
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) + col_offset
    x = r * jnp.int32(L) + c
    x = x ^ (seed + bh * jnp.int32(-1640531527))  # 2654435761 as int32
    return hash_uniform(x)


def hash_uniform(x):
    """int32 array -> uniform floats in [0, 1).

    3-stage finalizer (mul, xorshift, mul): two stages fewer than the full
    murmur3 tail — measured statistically indistinguishable for dropout
    (mean, row/col uniformity, adjacency correlation of the keep mask all
    match the 5-stage version), and the grids are regenerated per head per
    pass, so VPU ops here are hot. Shared with ring attention's in-flight
    dropout (ops/ring_attention.py), which keys the same finalizer by
    GLOBAL indices so its masks are shard-count invariant."""
    x = x * jnp.int32(-862048943)   # 0xCC9E2D51
    x = x ^ ((x >> 16) & jnp.int32(0xFFFF))
    x = x * jnp.int32(0x1B873593)
    u24 = (x >> 7) & jnp.int32(0x00FFFFFF)  # 24 uniform bits -> [0, 1)
    return u24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _allowed_grid(qmask, kmask, seg: bool):
    """[q_rows, k_rows] bool attend-permission grid from the mask operand.

    Unsegmented (``seg=False``): the historical key-only validity — every
    query row sees every valid key (``kmask > 0``). Segmented: the mask
    operand carries SEGMENT IDS (0 = pad, 1..S = packed segment) and the
    grid becomes block-diagonal — query i attends key j iff their ids match
    and are nonzero. Pad queries (id 0) match no valid key, so their rows
    softmax over all -inf and produce finite garbage that downstream
    masking ignores (the exact contract pad rows already have)."""
    if seg:
        return (qmask[:, None] == kmask[None, :]) & (kmask[None, :] > 0)
    return kmask[None, :] > 0


def _softmax_probs(q, k, mask, scale, *, allowed=None):
    """[L, L] f32 attention probabilities for one (batch, head)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale
    if allowed is None:
        allowed = mask[None, :] > 0
    s = jnp.where(allowed, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _fused_fwd_kernel(seed_ref, mask_ref, q_ref, k_ref, v_ref, o_ref,
                      *lse_ref, scale: float, rate: float, hc: int,
                      D: int, seg: bool = False):
    """One (batch, head-group) program: softmax(q k^T / sqrt(d)) v for ``hc``
    heads, with optional attention-probs dropout, fully in VMEM. Operands
    arrive FOLDED as [B, L, H*D] — contiguous with the encoder's natural
    [B, L, H, D] layout, so no relayout transposes surround the custom call
    (XLA cannot fuse a transpose INTO a custom call; the former [B,H,L,D]
    kernel layout cost 4 HBM round-trips of q/k/v/o per layer — measured
    10% of the bert-base train step). Heads are static lane slices of the
    folded block, looped unrolled; ``hc`` bounds the block so in/out
    double-buffers + [L, L] f32 temporaries fit VMEM.

    When a trailing ``lse_ref`` output ([1, 1, 1, hc*L] f32 — the
    head-major lane wire layout of ``_lse_pack``) is present, each row's
    logsumexp is also written — the backward kernels then recompute
    probabilities as ``exp(s - lse)`` without redoing the max/sum/divide
    normalization sweeps. The lane orientation costs one [L]-element
    relayout per head per program (column -> lane row) but keeps the
    saved-residual HBM tensor compact (see ``_lse_pack`` for why, and for
    the bert-large OOM the former [B, H, L, 1] layout caused)."""
    b, hj = pl.program_id(0), pl.program_id(1)
    mask = mask_ref[0, 0, :]
    allowed = _allowed_grid(mask, mask, seg)
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)
        q = q_ref[0, :, sl]
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(allowed, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        if lse_ref:
            rows = q.shape[0]
            lse_ref[0][0, 0, 0, h * rows:(h + 1) * rows] = (
                m + jnp.log(l)
            )[:, 0]  # [L] lane row at the head-major offset (_lse_pack)

        if rate > 0.0:
            u = _uniform_grid(seed_ref[b], hj * hc + h, q.shape[0])
            e = jnp.where(u >= rate, e * (1.0 / (1.0 - rate)), 0.0)

        # the softmax divide folds into a per-row scale of the [L, D]
        # output instead of a full [L, L] VPU pass over the probabilities
        o = jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (1.0 / l)
        o_ref[0, :, sl] = o.astype(o_ref.dtype)


def _attention_bwd_math(q, k, v, g, mask, scale, *, drop=None, lse=None,
                        out=None, allowed=None):
    """Exact softmax-attention backward for one head, probabilities
    recomputed in VMEM. ``q``/``g`` may be a q-block; ``k``/``v`` are the
    full rows. ``drop``: optional ``(keep_bool_grid, inv_rate)`` applying
    the forward's dropout in-kernel. ``lse``: optional [q_rows, 1] per-row
    logsumexp saved by the forward — probabilities then come from ONE
    ``exp(s - lse)`` instead of the max/sum/divide normalization sweeps.
    ``out``: optional [q_rows, D] forward output rows — the softmax-backward
    row term then comes from the FlashAttention-2 delta identity
    ``row_i = g_i . out_i`` (one [q_rows, D] multiply-reduce) instead of a
    full [q_rows, L] ``sum(dp * p)`` pass; the identity holds WITH dropout
    (sum_j keep*inv*dp_drop * p = sum_j dp_drop * p_drop = g.out — same
    derivation as ring_attention.py's backward).
    ``allowed``: optional [q_rows, L] bool attend-permission grid (the
    segment-aware block-diagonal mask); None keeps the key-only 1-D mask.
    Returns ``(dq, dk, dv)`` in f32, where dk/dv have k's row count."""
    if lse is not None:
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(
            allowed if allowed is not None else mask[None, :] > 0,
            s, _NEG_INF,
        )
        p = jnp.exp(s - lse)  # [q_rows, L] f32, pre-dropout
        if allowed is not None:
            # a segmented row can be ALL-masked (a pad query row): its lse
            # is then -1e30 itself and exp(s - lse) degenerates to 1 on the
            # very keys the mask forbids, leaking pad-row garbage into real
            # dk/dv. Zero disallowed entries explicitly — for healthy rows
            # exp(-1e30 - lse) is already 0, so this only cleans the
            # degenerate ones (their dq/dk/dv contributions become exactly
            # zero instead of garbage).
            p = jnp.where(allowed, p, 0.0)
    else:
        p = _softmax_probs(q, k, mask, scale, allowed=allowed)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
    if drop is not None:
        keep, inv = drop
        p_drop = jnp.where(keep, p * inv, 0.0)
    else:
        p_drop = p

    # dv = p_drop^T g
    dv = jax.lax.dot_general(
        p_drop.astype(g.dtype), g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dp_drop = g v^T
    dp_drop = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # dropout backward, then softmax backward
    if drop is not None:
        dp = jnp.where(keep, dp_drop * inv, 0.0)
    else:
        dp = dp_drop
    if out is not None:
        row = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
    else:
        row = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - row)  # f32; zero on masked keys since p is zero there

    dq = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dk = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    return dq, dk, dv


def _fused_bwd_kernel(seed_ref, mask_ref, q_ref, k_ref, v_ref, g_ref,
                      out_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                      *, scale: float, rate: float, hc: int,
                      D: int, seg: bool = False):
    """One (batch, head-group) program: exact attention backward for ``hc``
    heads, recomputing the probabilities from the forward's saved per-row
    logsumexp (and regenerating the identical dropout mask) in VMEM; the
    softmax row term comes from the saved forward output via the delta
    identity (one [L, D] pass instead of an [L, L] one).
    Folded [B, L, H*D] layout like the forward."""
    b, hj = pl.program_id(0), pl.program_id(1)
    mask = mask_ref[0, 0, :]
    allowed = _allowed_grid(mask, mask, seg) if seg else None
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)
        q = q_ref[0, :, sl]
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]
        g = g_ref[0, :, sl]

        drop = None
        if rate > 0.0:
            keep = _uniform_grid(
                seed_ref[b], hj * hc + h, q.shape[0]
            ) >= rate
            drop = (keep, jnp.float32(1.0 / (1.0 - rate)))

        rows = q.shape[0]
        dq, dk, dv = _attention_bwd_math(
            q, k, v, g, mask, scale, drop=drop,
            lse=lse_ref[0, 0, 0, h * rows:(h + 1) * rows][:, None],
            out=out_ref[0, :, sl], allowed=allowed,
        )

        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


def _blocked_bwd_kernel(seed_ref, mask_ref, q_ref, k_ref, v_ref, g_ref,
                        out_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                        *, scale: float, rate: float, hc: int,
                        D: int, seg: bool = False):
    """Fused long-sequence backward: one (batch, head-group, q-block)
    program. The whole K/V for the head group stays resident in VMEM; each
    program recomputes its q rows' EXACT probabilities from the forward's
    saved per-row logsumexp and the full [q_blk, L] score gradient.
    dq writes its own q-block; dk/dv accumulate in f32 into output blocks
    whose index map is constant in the q-block dimension — Pallas keeps
    them resident across the q sweep and writes back once per (b, hj).
    Dropout (``rate > 0``) regenerates the forward's keep-mask from the
    absolute row indices of this q-block."""
    b, hj, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    mask = mask_ref[0, 0, :]
    L = k_ref.shape[1]
    q_blk = q_ref.shape[1]
    allowed = None
    if seg:
        # the mask block is the WHOLE row (its index map is constant in qi),
        # so this q-block's segment ids are a dynamic slice of it
        qmask = mask_ref[0, 0, pl.ds(qi * q_blk, q_blk)]
        allowed = _allowed_grid(qmask, mask, seg)
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)

        drop = None
        if rate > 0.0:
            keep = _uniform_grid(
                seed_ref[b], hj * hc + h, L,
                rows=q_blk, row_offset=qi * q_blk,
            ) >= rate
            drop = (keep, jnp.float32(1.0 / (1.0 - rate)))

        dq, dk, dv = _attention_bwd_math(
            q_ref[0, :, sl],   # [q_blk, D]
            k_ref[0, :, sl],   # [L, D] (whole)
            v_ref[0, :, sl],   # [L, D] (whole)
            g_ref[0, :, sl],   # [q_blk, D]
            mask, scale, drop=drop,
            lse=lse_ref[0, 0, 0, h * q_blk:(h + 1) * q_blk][:, None],
            out=out_ref[0, :, sl],  # [q_blk, D]
            allowed=allowed,
        )

        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)

        @pl.when(qi == 0)
        def _init():
            dk_ref[0, :, sl] = dk
            dv_ref[0, :, sl] = dv

        @pl.when(qi > 0)
        def _accum():
            dk_ref[0, :, sl] += dk
            dv_ref[0, :, sl] += dv


def _blocked_fwd_kernel(seed_ref, mask_ref, q_ref, k_ref, v_ref, o_ref,
                        *lse_ref, scale: float, rate: float, hc: int,
                        D: int, seg: bool = False):
    """One (batch, head-group, q-block) program for longer sequences, with
    optional in-kernel attention-probs dropout (keep-bits keyed by the
    absolute row index so the backward regenerates the same mask). A
    trailing ``lse_ref`` output — the ``(1, 1, 1, hc*q_blk)`` head-major
    lane wire block of ``_lse_pack`` (lane = h*q_blk + row) — saves each
    row's logsumexp for the backward, like the fused kernel's."""
    b, hj, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    mask = mask_ref[0, 0, :]
    L = k_ref.shape[1]
    q_blk = q_ref.shape[1]
    if seg:
        qmask = mask_ref[0, 0, pl.ds(qi * q_blk, q_blk)]
        allowed = _allowed_grid(qmask, mask, seg)
    else:
        allowed = _allowed_grid(mask, mask, seg)  # [1, L] broadcast
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)
        q = q_ref[0, :, sl]
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(allowed, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        if lse_ref:
            lse_ref[0][0, 0, 0, h * q_blk:(h + 1) * q_blk] = (
                m + jnp.log(l)
            )[:, 0]  # [q_blk] lane row at the head-major offset (_lse_pack)
        if rate > 0.0:
            u = _uniform_grid(
                seed_ref[b], hj * hc + h, L,
                rows=q_blk, row_offset=qi * q_blk,
            )
            e = jnp.where(u >= rate, e * (1.0 / (1.0 - rate)), 0.0)
        # softmax divide folded into a per-row scale of the [q_blk, D]
        # output instead of a [q_blk, L] VPU pass
        o = jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (1.0 / l)
        o_ref[0, :, sl] = o.astype(o_ref.dtype)


def _pick_q_block(L: int) -> Optional[int]:
    for blk in (512, 256, 128):
        if L % blk == 0:
            return blk
    if L <= 512:
        return L  # single block
    return None


def supports_fused_bwd(L: int, interpret: bool = False) -> bool:
    """True when the fully-fused fwd+bwd (and therefore dropout) applies.

    On a compiled TPU backend the length is additionally gated on
    ``L % 128 == 0`` (ADVICE r5 #1): the head-major lse wire block slices
    lanes at offsets ``h*L`` with width ``hc*L``, and Mosaic requires
    128-aligned lane slices on hardware — a constraint interpret mode never
    checks, so e.g. L=264 passes every interpret-mode test and then fails to
    lower on a real chip. Interpret/CPU keeps the old envelope so tier-1
    behavior is unchanged; such lengths route to the XLA path on hardware.
    """
    if not (L <= _FUSED_BWD_MAX_LEN and _pick_q_block(L) is not None):
        return False
    if interpret or jax.default_backend() != "tpu":
        return True
    return L % 128 == 0


def _sublane8(n: int) -> int:
    """Round a sublane count up to the (8, 128)-tile granularity — the
    VMEM footprint of an [n, lanes] f32 block."""
    return ((n + 7) // 8) * 8


def _dtype_for_itemsize(itemsize: int, dtype=None):
    """Dtype for an autotune probe key when the caller only knows the
    itemsize (the ``supports_*`` dispatcher signatures): an explicit dtype
    wins; otherwise 2 -> bf16, anything else -> f32 — the two itemsizes the
    kernels actually carry."""
    if dtype is not None:
        return jnp.dtype(dtype)
    return jnp.dtype(jnp.bfloat16) if itemsize == 2 else jnp.dtype(jnp.float32)


def _lse_pack(lse, qb: int):
    """[B, H, L] -> the kernel wire layout [B, L//qb, 1, H*qb].

    The kernels cannot block a [B, H, L] tensor directly: a (1, hc, qb)
    block needs its sublane dim hc divisible by 8 or equal to H, which the
    legal head chunks (e.g. hc=6 at bert-base) violate. In the wire layout
    the lane dim is HEAD-MAJOR (lane = h*qb + row) and the dim of 1 makes
    any (1, 1, 1, hc*qb) block legal, with every in-kernel slice static.
    The pack/unpack are XLA reshape+transpose of the COMPACT [B, H, L]
    residual (~1.5 MB at bert-base) — the tensor that stays live across
    the whole backward is never padded (the former [B, H, L, 1] layout
    lane-padded every (8, 128) tile 128x, ~200 MB of HBM allocation and
    whole-tile DMA traffic per bert-base layer-micro, and OOM'd bert-large
    — round-5 on-chip capture, artifacts/r4/bench_bert_large.log)."""
    B, H, L = lse.shape
    return (lse.reshape(B, H, L // qb, qb)
            .transpose(0, 2, 1, 3)
            .reshape(B, L // qb, 1, H * qb))


def _lse_unpack(lse_packed, qb: int, H: int):
    """Inverse of ``_lse_pack``: [B, L//qb, 1, H*qb] -> [B, H, L]."""
    B, nq = lse_packed.shape[0], lse_packed.shape[1]
    return (lse_packed.reshape(B, nq, H, qb)
            .transpose(0, 2, 1, 3)
            .reshape(B, H, nq * qb))


def _fold(x):
    """[B, L, H, D] -> [B, L, H*D]: contiguous, so XLA lowers it to a free
    bitcast (unlike the [B,H,L,D] relayout, which is a real HBM copy)."""
    B, L, H, D = x.shape
    return x.reshape(B, L, H * D)


def _row_seeds(seed, B: int, H: int, first_row=None):
    """Per-batch-row int32 seed vector for the scalar-prefetch operand.

    Row ``r`` continues the scalar scheme exactly (``seed + r*H*PRIME``), so
    single-shard masks are bit-identical to the former scalar seeding; but
    because the kernels key by ``seed_ref[b]``, a batch-sharded execution
    hands each shard its rows' GLOBAL seeds, and the masks are those of the
    unsharded call: ``ops/attention.sharded_kernel_call`` passes the global
    [B] vector sharded with the batch, a caller already inside one shard
    (``B`` local rows) the global index of its first row as ``first_row``."""
    if seed.shape[0] == B and B > 1:
        return seed.astype(jnp.int32)
    first = seed[0].astype(jnp.int32)
    rows = jax.lax.iota(jnp.int32, B)
    if first_row is not None:
        rows = rows + first_row
    return first + rows * (jnp.int32(H) * jnp.int32(-1640531527))


_VMEM_BUDGET = 12 * 1024 * 1024  # leave ~4 MB of the ~16 MB/core for Mosaic

# Batch size of every compile probe. NOT 1: with a one-step grid (B=1 and a
# single head group) Mosaic allocates no second pipeline buffer, so the probe
# approves geometries that overflow scoped VMEM at any real batch. Compile-only
# evidence against a described v5e (jax 0.9.0 / libtpu 0.0.34, PR 21): fused
# bwd L=512 hc=12 and q-blocked fwd L=1024 (256, 12) compile at B=1 and are
# refused at B=2 and B=32 with the identical overflow (21.16M / 18.32M vs
# the 16M limit). From two grid steps on, the verdict is batch-independent.
_PROBE_BATCH = 2


# Scoped-VMEM ceiling per ``device_kind``: the largest f32 scratch block the
# installed compiler accepts in one Pallas kernel (it enforces "limit 16.00M"
# on v5e; a trivial kernel's own tiles take the rest). v5e: bisected with
# scripts/measure_vmem_ceiling.py, compile-only against a described v5e:2x2
# under jax 0.9.0 / libtpu 0.0.34 (PR 21). A kind that is not listed is an
# error on the compiled path: measure it and add the row.
_SCOPED_VMEM_CEILING = {
    "TPU v5 lite": 16_715_776,
}
_ARITHMETIC_ONLY_KIND = "TPU v5 lite"  # CPU / interpret: nothing is compiled


def _scoped_vmem_ceiling(device_kind: Optional[str] = None,
                         xla_flags: Optional[str] = None) -> int:
    """Scoped-VMEM ceiling the fused backward budgets against.

    An explicit ``xla_tpu_scoped_vmem_limit_kib`` in ``XLA_FLAGS`` wins — the
    operator overrode the limit, so the arithmetic must follow. Otherwise the
    ``_SCOPED_VMEM_CEILING`` row of ``device_kind``; ``None`` (no TPU: the
    arithmetic only ranks, nothing is compiled) takes the v5e row.

    The result is clamped to >= ``_VMEM_BUDGET`` + 1 MiB: below that the
    "aggressive" fused-bwd budget would drop under the conservative 12 MB
    paper budget, inverting the probe's conservative-refuge ordering.
    Ceilings that small are outside this kernel's supported envelope — the
    compile probe is the gate that actually protects such a chip.
    """
    import os as _os
    import re as _re

    floor = _VMEM_BUDGET + 1024 * 1024
    if xla_flags is None:
        xla_flags = _os.environ.get("XLA_FLAGS", "")
    m = _re.search(r"xla_tpu_scoped_vmem_limit_kib=(\d+)", xla_flags)
    if m:
        return max(int(m.group(1)) * 1024, floor)
    kind = _ARITHMETIC_ONLY_KIND if device_kind is None else device_kind
    if kind not in _SCOPED_VMEM_CEILING:
        raise RuntimeError(
            f"no scoped-VMEM ceiling on record for device_kind {kind!r}: "
            f"run scripts/measure_vmem_ceiling.py there and add the row to "
            f"_SCOPED_VMEM_CEILING"
        )
    return max(_SCOPED_VMEM_CEILING[kind], floor)


def _fused_bwd_budget() -> int:
    """The fully-fused backward budgets against the attached chip's scoped-
    VMEM ceiling instead of the conservative 12 MB paper budget: its
    accounting counts every block (including the sublane-padded lse input),
    and a compile probe (``_fused_bwd_hc``) backstops the arithmetic on real
    hardware, so the margin the paper budget buys is provided by the probe
    instead. Resolved at trace time, never at import (importing this module
    must not initialise a backend)."""
    kind = (
        jax.devices()[0].device_kind
        if jax.default_backend() == "tpu" else None
    )
    return _scoped_vmem_ceiling(kind) - 1024 * 1024


def _legal_head_chunks(H: int, D: int):
    """Divisors of H whose lane width (hc*D) is 128-divisible or spans the
    whole folded array (Mosaic rejects other block widths — hc=3 with D=64
    gives 192 lanes and fails to lower)."""
    return [
        d for d in range(1, H + 1)
        if H % d == 0 and ((d * D) % 128 == 0 or d == H)
    ]


def _pick_head_chunk(H: int, D: int, bytes_per_head: int,
                     temp_bytes: int, budget: int = _VMEM_BUDGET) -> int:
    """Largest legal divisor of H whose per-head-group block bytes plus the
    fixed temporaries fit the VMEM budget. Callers compute
    ``bytes_per_head`` from their own block geometry and dtypes (x2 for
    Mosaic double-buffering) and ``temp_bytes`` from their per-head f32
    working set. Falls back to the smallest legal chunk when nothing fits
    the budget (best effort — Mosaic may still OOM loudly)."""
    legal = _legal_head_chunks(H, D)
    for hc in sorted(legal, reverse=True):
        if bytes_per_head * hc + temp_bytes <= budget:
            return hc
    return min(legal)


def _build_fused_fwd_call(B, L, H, D, in_dtype, out_dtype, rate, hc,
                          interpret, want_lse, seg=False):
    """The forward ``pallas_call`` for one head-chunk choice, shared by the
    execution path and the autotuner's compile probe so they cannot drift."""
    spec_lf = pl.BlockSpec((1, L, hc * D), lambda b, hj, *_: (b, 0, hj))

    out_specs = [spec_lf]
    out_shape = [jax.ShapeDtypeStruct((B, L, H * D), out_dtype)]
    if want_lse:
        # head-major wire layout (see _lse_pack): qb = L here (one q block)
        out_specs.append(
            pl.BlockSpec((1, 1, 1, hc * L), lambda b, hj, *_: (b, 0, 0, hj))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((B, 1, 1, H * L), jnp.float32)
        )

    return pl.pallas_call(
        functools.partial(_fused_fwd_kernel, scale=1.0 / (D ** 0.5),
                          rate=rate, hc=hc, D=D, seg=seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hc),
            in_specs=[
                pl.BlockSpec((1, 1, L), lambda b, hj, *_: (b, 0, 0)),  # mask
                spec_lf, spec_lf, spec_lf,                             # q k v
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
    )


def _fused_fwd_analytic_hc(L, H, D, in_itemsize, out_itemsize,
                           want_lse, seg=False) -> int:
    """The pre-autotuner arithmetic pick for the fused forward (kept as the
    autotuner's ranking prior and its no-probe fallback)."""
    return _pick_head_chunk(
        H, D,
        # the (1, 1, 1, hc*L) lse wire block occupies 8 sublanes x hc*L
        # lanes of f32 in VMEM (dim-of-1 pads to the 8-row tile floor),
        # double-buffered: exactly 2*8*L*4 bytes per head
        bytes_per_head=2 * L * D * (3 * in_itemsize + out_itemsize)
        + (2 * _sublane8(1) * L * 4 if want_lse else 0),
        # scores/probs/dropout-uniform f32, + the [L, L] block-diagonal
        # permission grid when segment-aware
        temp_bytes=(3 + (1 if seg else 0)) * L * L * 4,
    )


def _seg_extra(mask_dtype, seg: bool) -> str:
    """Autotune key suffix: segment-aware kernels are DIFFERENT programs
    (block-diagonal mask grid) — their cached geometry must not collide
    with the key-mask variants'."""
    base = f"mask{jnp.dtype(mask_dtype)}"
    return base + ("-seg" if seg else "")


def _no_head_chunk_compiled(direction: str, L, H, D) -> RuntimeError:
    """Every legal head chunk of a fused kernel was refused by the compile
    probe: there is nothing to run, and handing back an unvalidated chunk
    would only move the failure into the train step's compile."""
    return RuntimeError(
        f"fused attention {direction}: the compiler refused every legal head "
        f"chunk {_legal_head_chunks(H, D)} at L={L}, H={H}, D={D} (see the "
        f"probe warnings above); this shape cannot run the fused kernels on "
        f"this device"
    )


def _fused_fwd_hc(B, L, H, D, in_dtype, mask_dtype, out_dtype, rate,
                  want_lse, interpret, seg=False) -> int:
    """Head-chunk selection for the fused forward, through the autotuner:
    probe-validated on TPU, the old arithmetic elsewhere."""
    in_isz = jnp.dtype(in_dtype).itemsize
    out_isz = jnp.dtype(out_dtype).itemsize

    def analytic():
        return _fused_fwd_analytic_hc(L, H, D, in_isz, out_isz, want_lse,
                                      seg=seg)

    def cost(hc):
        # fewer head-groups = fewer grid programs and fewer k/v streams;
        # per-group block bytes scale with hc either way
        return H // hc

    def probe(hc):
        args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 3,
        ]
        call = _build_fused_fwd_call(_PROBE_BATCH, L, H, D, in_dtype,
                                     out_dtype, rate,
                                     hc, interpret=False, want_lse=want_lse,
                                     seg=seg)
        return _probe_compiles(call, args,
                               aggressive=cost(hc) < cost(analytic()))

    hc = autotune.get().select(
        "fused_fwd_lse" if want_lse else "fused_fwd",
        L=L, H=H, D=D, in_dtype=jnp.dtype(in_dtype), out_dtype=out_dtype,
        dropout=rate > 0.0, extra=_seg_extra(mask_dtype, seg),
        candidates=sorted(_legal_head_chunks(H, D), reverse=True),
        cost=cost, probe=probe, analytic=analytic, interpret=interpret, batch=_PROBE_BATCH,
    )
    if hc is None:
        raise _no_head_chunk_compiled("forward", L, H, D)
    return hc


def _flash_forward(q, k, v, mask, seed, dtype, rate, interpret: bool,
                   want_lse: bool = False, seg: bool = False):
    B, L, H, D = q.shape
    if want_lse and not interpret:
        # compiled-path invariant behind supports_fused_bwd's L % 128 gate
        # (ADVICE r5 #1): the head-major lse wire block needs 128-aligned
        # lane slices on hardware
        assert L % 128 == 0 or jax.default_backend() != "tpu", (
            f"fused want_lse path needs L % 128 == 0 on TPU, got L={L}; "
            f"gate on supports_fused_bwd"
        )
    hc = _fused_fwd_hc(B, L, H, D, q.dtype, mask.dtype, jnp.dtype(dtype),
                       rate, want_lse, interpret, seg=seg)
    res = _build_fused_fwd_call(B, L, H, D, q.dtype, dtype, rate, hc,
                                interpret, want_lse, seg=seg)(
        _row_seeds(seed, B, H), mask[:, None, :], _fold(q), _fold(k), _fold(v)
    )
    if want_lse:
        return res[0].reshape(B, L, H, D), _lse_unpack(res[1], L, H)
    return res[0].reshape(B, L, H, D)


def _fused_bwd_bytes_per_head(L: int, D: int, itemsize: int,
                              out_itemsize: int) -> int:
    """Per-head double-buffered block bytes of the fused backward: seven
    [L, hc*D] blocks in the input dtype (q k v g dq dk dv), the out block in
    the FORWARD OUTPUT dtype (delta-identity row term), and the (1, 1, 1,
    hc*L) lse wire block (8 sublanes x hc*L lanes of f32 in VMEM — exactly
    2*8*L*4 per head) — EVERY block counted at its own itemsize, same
    discipline as the forward and blocked cfgs."""
    return (2 * L * D * 7 * itemsize + 2 * L * D * out_itemsize
            + 2 * _sublane8(1) * L * 4)


# s/p/keep/dp/ds f32 working set, in [L, L] units (the delta-identity row
# term reads the [L, D] out block instead of materializing a dp*p grid)
_FUSED_BWD_TEMPS = 5


def _build_fused_bwd_call(B, L, H, D, in_dtype, rate, hc, interpret,
                          seg=False):
    """The backward ``pallas_call`` for one head-chunk choice, shared by the
    real execution path and the compile probe so they cannot drift."""
    spec_lf = pl.BlockSpec((1, L, hc * D), lambda b, hj, *_: (b, 0, hj))
    return pl.pallas_call(
        functools.partial(_fused_bwd_kernel, scale=1.0 / (D ** 0.5),
                          rate=rate, hc=hc, D=D, seg=seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hc),
            in_specs=[
                pl.BlockSpec((1, 1, L), lambda b, hj, *_: (b, 0, 0)),  # mask
                spec_lf, spec_lf, spec_lf, spec_lf, spec_lf,   # q k v g out
                pl.BlockSpec((1, 1, 1, hc * L),
                             lambda b, hj, *_: (b, 0, 0, hj)),  # lse wire
            ],
            out_specs=[spec_lf, spec_lf, spec_lf],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, L, H * D), in_dtype)] * 3,
        interpret=interpret,
        name="flash_bwd",
    )


def _looks_like_vmem_overflow(err: Exception) -> bool:
    # Only a message that names VMEM counts. The installed compiler words a
    # kernel's fast-memory overflow "RESOURCE_EXHAUSTED: Ran out of memory in
    # memory space vmem ... exceeded scoped vmem limit" (jax 0.9.0 / libtpu
    # 0.0.34); a bare "resource_exhausted" / "out of memory" match would also
    # swallow an HBM failure ("memory space hbm") as a too-big block, and a
    # bare "exceeds" hc-independent Mosaic errors ("block shape exceeds array
    # bounds"), turning a real kernel bug into a silent walk-down of head
    # chunks. An UNRECOGNIZED wording at an aggressive-budget pick falls back
    # to the conservative 12 MB-budget chunk with a warning (_probe_compiles).
    return "vmem" in str(err).lower()


def _probe_compiles(call, arg_shapes, *, aggressive: bool):
    """AOT-compile one candidate's ``pallas_call`` (fresh ShapeDtypeStructs,
    no tracers — safe inside an outer trace) and classify the outcome:

    - compiles: the candidate is legal — the COMPILED object is returned so
      the autotuner can rank legal candidates by their
      ``cost_analysis()`` estimates instead of the analytic prior alone
      (ops/autotune.py ``_probe_ranked``; ROADMAP raw-speed item b);
    - a recognized VMEM-overflow wording: infeasible, the autotuner walks to
      the next-ranked candidate;
    - an UNCLASSIFIED compile error at an ``aggressive`` candidate (one
      ranked cheaper than the analytic arithmetic's own pick — a jaxlib may
      word its overflow in a way ``_looks_like_vmem_overflow`` does not
      know): warn and treat as infeasible, so selection degrades to the
      arithmetic's refuge instead of dying (ADVICE r4 #1);
    - an unclassified error AT or BELOW the analytic pick: a genuine kernel
      bug — re-raise rather than silently routing the shape off-kernel.
    """
    try:
        # hlo-keyed AOT store routing: each candidate's compiled probe
        # persists under its own program hash, so a warm restart (or a
        # cleared tuning cache on an unchanged toolchain) loads the
        # probes instead of re-paying Mosaic compiles
        return aot.probe_compile("attn-probe", call, *arg_shapes)
    except Exception as e:  # noqa: BLE001 - classified below
        if _looks_like_vmem_overflow(e):
            return False
        if aggressive:
            import logging
            logging.getLogger(__name__).warning(
                "autotune compile probe: unclassified compile error at an "
                "aggressive candidate; treating as infeasible and walking "
                "to the analytic refuge. Error: %s", e,
            )
            return False
        raise


def _fused_bwd_hc(B, L, H, D, in_dtype, mask_dtype, out_dtype, rate,
                  interpret, seg=False) -> int:
    """Head-chunk choice for the fused backward, through the autotuner: on
    real TPU every candidate is ranked by modeled cost and validated with a
    cached compile probe (VERDICT r3 #3: feasibility must not depend on a
    comment); interpret/CPU keeps the aggressive-budget arithmetic pick
    (nothing to probe: interpret mode cannot OOM VMEM).

    The probe AOT-compiles the SAME pallas_call the execution path uses
    (fresh ShapeDtypeStructs, no tracers) at ``_PROBE_BATCH`` — from two
    grid steps on, scoped VMEM is batch-independent, so one verdict covers
    every batch size — and winners persist in the on-disk tuning cache,
    amortized further by the persistent compilation cache across processes.

    An unclassified compile error at a candidate MORE aggressive than the
    conservative 12 MB paper-budget pick is abandoned with a warning (the
    walk reaches the conservative refuge next); at or below that pick it is
    a genuine kernel bug and raises (ADVICE r4 #1).
    """
    itemsize = jnp.dtype(in_dtype).itemsize
    out_isz = jnp.dtype(out_dtype).itemsize

    def pick(budget):
        return _pick_head_chunk(
            H, D,
            bytes_per_head=_fused_bwd_bytes_per_head(L, D, itemsize, out_isz),
            # + the [L, L] block-diagonal permission grid when segment-aware
            temp_bytes=(_FUSED_BWD_TEMPS + (1 if seg else 0)) * L * L * 4,
            budget=budget,
        )

    def analytic():
        if not interpret and jax.default_backend() == "tpu":
            # probing unavailable (autotune disabled): without the probe
            # backstop the aggressive ceiling budget is unsafe — take the
            # conservative paper-budget pick
            return pick(_VMEM_BUDGET)
        return pick(_fused_bwd_budget())

    def cost(hc):
        return H // hc

    def probe(hc):
        conservative = pick(_VMEM_BUDGET)
        args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 4,
            jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), out_dtype),  # out
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, 1, H * L), jnp.float32),
        ]
        call = _build_fused_bwd_call(_PROBE_BATCH, L, H, D, in_dtype, rate, hc,
                                     interpret=False, seg=seg)
        return _probe_compiles(call, args,
                               aggressive=cost(hc) < cost(conservative))

    hc = autotune.get().select(
        "fused_bwd",
        L=L, H=H, D=D, in_dtype=jnp.dtype(in_dtype), out_dtype=out_dtype,
        dropout=rate > 0.0, extra=_seg_extra(mask_dtype, seg),
        candidates=sorted(_legal_head_chunks(H, D), reverse=True),
        cost=cost, probe=probe, analytic=analytic, interpret=interpret, batch=_PROBE_BATCH,
    )
    if hc is None:
        raise _no_head_chunk_compiled("backward", L, H, D)
    return hc


def _flash_backward(q, k, v, mask, seed, g, out, lse, dtype, rate,
                    interpret: bool, seg: bool = False):
    B, L, H, D = q.shape
    hc = _fused_bwd_hc(B, L, H, D, q.dtype, mask.dtype, out.dtype, rate,
                       interpret, seg=seg)
    dq, dk, dv = _build_fused_bwd_call(B, L, H, D, q.dtype, rate, hc,
                                       interpret, seg=seg)(
        _row_seeds(seed, B, H), mask[:, None, :], _fold(q), _fold(k),
        _fold(v), _fold(g), _fold(out), _lse_pack(lse, L))
    return tuple(x.reshape(B, L, H, D) for x in (dq, dk, dv))


def _blocked_fwd_cfg(L: int, H: int, D: int, in_itemsize: int,
                     out_itemsize: int, rate: float = 0.0,
                     seg: bool = False):
    """(q_blk, hc) for the q-blocked forward, or ``None`` when no
    configuration fits the VMEM budget (the dispatcher then routes to the
    XLA path instead of letting Mosaic OOM on hardware — interpret-mode
    tests cannot catch a real VMEM overflow).

    Working set per program: [q_blk, L] f32 temporaries (scores, probs,
    softmax scratch, + the dropout uniform grid when ``rate > 0``); blocks:
    q at q_blk rows and k/v at L rows (input dtype), o at q_blk rows
    (output dtype), all double-buffered."""
    q_blk = _pick_q_block(L)
    if q_blk is None:
        return None
    # + the [q_blk, L] block-diagonal permission grid when segment-aware
    n_temps = 3 + (1 if rate > 0.0 else 0) + (1 if seg else 0)
    while q_blk > 128 and n_temps * q_blk * L * 4 > _VMEM_BUDGET // 2:
        q_blk //= 2
    temp_bytes = n_temps * q_blk * L * 4
    for hc in sorted(_legal_head_chunks(H, D), reverse=True):
        block_bytes = hc * D * 2 * (
            (2 * L + q_blk) * in_itemsize + q_blk * out_itemsize
        )
        # the (1, 1, 1, hc*q_blk) lse wire output block (training forwards
        # save per-row logsumexp for the backward): 8 sublanes x hc*q_blk
        # lanes of f32, double-buffered. Counted always so the feasibility
        # gates cover the training path.
        block_bytes += hc * 2 * _sublane8(1) * q_blk * 4
        if block_bytes + temp_bytes <= _VMEM_BUDGET:
            return q_blk, hc
    return None


def _blocked_candidates(L: int, H: int, D: int):
    """All (q_blk, hc) geometry candidates of the q-blocked regime (the
    autotuner's enumeration; the analytic cfgs walk the same space)."""
    q_blks = [blk for blk in (512, 256, 128) if L % blk == 0]
    if not q_blks and L <= 512:
        q_blks = [L]
    return [(q_blk, hc) for q_blk in q_blks
            for hc in sorted(_legal_head_chunks(H, D), reverse=True)]


def _blocked_cost(L: int, H: int, D: int):
    """Modeled step cost of a (q_blk, hc) candidate: grid programs dominate
    (K/V stay resident per (b, hj), so HBM traffic is nearly geometry-
    invariant); ties break toward larger head chunks (wider MXU feeds)."""
    def cost(geom):
        q_blk, hc = geom
        return ((H // hc) * (L // q_blk), H // hc)
    return cost


def _blocked_fwd_geometry(L, H, D, in_dtype, out_dtype, rate,
                          mask_dtype=jnp.int32, interpret=False,
                          seg=False):
    """(q_blk, hc) for the q-blocked forward through the autotuner, or
    ``None`` when no configuration is legal. Probed WITH the lse wire
    output (the training superset — the analytic cfg counts it always for
    the same reason)."""
    in_isz = jnp.dtype(in_dtype).itemsize
    out_isz = jnp.dtype(out_dtype).itemsize

    def analytic():
        return _blocked_fwd_cfg(L, H, D, in_isz, out_isz, rate, seg=seg)

    cost = _blocked_cost(L, H, D)

    def probe(geom):
        q_blk, hc = geom
        args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 3,
        ]
        call = _build_blocked_fwd_call(_PROBE_BATCH, L, H, D, in_dtype,
                                       out_dtype,
                                       rate, q_blk, hc, interpret=False,
                                       want_lse=True, seg=seg)
        ref = analytic()
        return _probe_compiles(
            call, args,
            aggressive=ref is None or cost(geom) < cost(ref),
        )

    return autotune.get().select(
        "blocked_fwd",
        L=L, H=H, D=D, in_dtype=jnp.dtype(in_dtype), out_dtype=out_dtype,
        dropout=rate > 0.0, extra=_seg_extra(mask_dtype, seg),
        candidates=_blocked_candidates(L, H, D), cost=cost, probe=probe,
        analytic=analytic, interpret=interpret, batch=_PROBE_BATCH,
    )


def supports_blocked_fwd(L: int, H: int, D: int, in_itemsize: int,
                         out_itemsize: int, rate: float = 0.0,
                         in_dtype=None, out_dtype=None,
                         mask_dtype=jnp.int32, segmented=False) -> bool:
    """True when the q-blocked forward has a feasible configuration for
    this exact shape/dtype geometry (no defaults: a bert-base answer for a
    different geometry would be silently wrong). On TPU the answer is the
    autotuner's (compile-probe-validated, cached); elsewhere the analytic
    arithmetic, unchanged. Optional ``in_dtype``/``out_dtype``/``mask_dtype``
    refine the probe key to match the execution path's (derived from the
    itemsizes / int32 when absent) — a dispatcher answer keyed differently
    from the execution selection could disagree with it. ``segmented``
    keys the block-diagonal (sequence-packing) kernel variant."""
    if L <= _FUSED_BWD_MAX_LEN:
        return False
    return _blocked_fwd_geometry(
        L, H, D,
        _dtype_for_itemsize(in_itemsize, in_dtype),
        _dtype_for_itemsize(out_itemsize, out_dtype),
        rate,
        mask_dtype=mask_dtype,
        seg=segmented,
    ) is not None


def _build_blocked_fwd_call(B, L, H, D, in_dtype, out_dtype, rate, q_blk,
                            hc, interpret, want_lse, seg=False):
    """The q-blocked forward ``pallas_call`` for one geometry, shared by the
    execution path and the autotuner's compile probe so they cannot drift."""
    out_specs = [
        pl.BlockSpec((1, q_blk, hc * D), lambda b, hj, qi, *_: (b, qi, hj))
    ]
    out_shape = [jax.ShapeDtypeStruct((B, L, H * D), out_dtype)]
    if want_lse:
        # head-major wire layout (see _lse_pack): qb = q_blk here
        out_specs.append(
            pl.BlockSpec((1, 1, 1, hc * q_blk),
                         lambda b, hj, qi, *_: (b, qi, 0, hj))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((B, L // q_blk, 1, H * q_blk), jnp.float32)
        )

    # q-blocks INNERMOST: the k/v index map is constant in qi, so Pallas
    # keeps each head-group's full K/V resident across all q-blocks instead
    # of re-streaming them L/q_blk times from HBM.
    return pl.pallas_call(
        functools.partial(_blocked_fwd_kernel, scale=1.0 / (D ** 0.5),
                          rate=rate, hc=hc, D=D, seg=seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hc, L // q_blk),
            in_specs=[
                pl.BlockSpec((1, 1, L), lambda b, hj, qi, *_: (b, 0, 0)),            # mask
                pl.BlockSpec((1, q_blk, hc * D), lambda b, hj, qi, *_: (b, qi, hj)),  # q
                pl.BlockSpec((1, L, hc * D), lambda b, hj, qi, *_: (b, 0, hj)),       # k
                pl.BlockSpec((1, L, hc * D), lambda b, hj, qi, *_: (b, 0, hj)),       # v
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
    )


def _blocked_forward(q, k, v, mask, seed, q_blk, hc, dtype, rate,
                     interpret: bool, want_lse: bool = False,
                     seg: bool = False):
    B, L, H, D = q.shape
    res = _build_blocked_fwd_call(B, L, H, D, q.dtype, dtype, rate, q_blk,
                                  hc, interpret, want_lse, seg=seg)(
        _row_seeds(seed, B, H), mask[:, None, :], _fold(q), _fold(k), _fold(v)
    )
    if want_lse:
        return res[0].reshape(B, L, H, D), _lse_unpack(res[1], q_blk, H)
    return res[0].reshape(B, L, H, D)


def _blocked_bwd_cfg(L: int, H: int, D: int, in_itemsize: int,
                     rate: float = 0.0, out_itemsize: int | None = None,
                     seg: bool = False):
    """(q_blk, hc) for the fused q-blocked backward, or ``None`` when no
    configuration fits the VMEM budget (the caller then falls back to the
    XLA-recompute backward instead of letting Mosaic OOM on hardware).

    Working set per program: [q_blk, L] f32 temporaries — 3 live grids
    (p, dp, ds; the delta-identity row term needs no dp*p grid) PLUS one
    grid of deliberate margin, because unlike the fused path this path has
    NO compile probe: the paper arithmetic is the only gate, so it must not
    run the budget to the wire — + the dropout keep grid when ``rate > 0``;
    blocks: q/g/dq at q_blk rows and k/v at L rows (input dtype), out at
    q_blk rows in the FORWARD OUTPUT dtype, all double-buffered; dk/dv at L
    rows in f32 (revisited accumulators, not double-buffered)."""
    if out_itemsize is None:
        out_itemsize = in_itemsize
    q_blk0 = _pick_q_block(L)
    if q_blk0 is None:
        return None
    # + the [q_blk, L] block-diagonal permission grid when segment-aware
    n_temps = 4 + (1 if rate > 0.0 else 0) + (1 if seg else 0)
    while q_blk0 > 128 and n_temps * q_blk0 * L * 4 > _VMEM_BUDGET // 2:
        q_blk0 //= 2
    # outer q_blk walk: a q-block that satisfies the temp budget can still
    # blow the BLOCK budget once the per-row streams (q/g/out/dq + lse) are
    # added — shrink further before declining the shape entirely
    q_blk = q_blk0
    while q_blk >= 128:
        temp_bytes = n_temps * q_blk * L * 4
        for hc in sorted(_legal_head_chunks(H, D), reverse=True):
            block_bytes = hc * D * (
                2 * (2 * L + 3 * q_blk) * in_itemsize
                + 2 * q_blk * out_itemsize + 2 * L * 4
            )
            # (1, 1, 1, hc*q_blk) lse wire input block (see fwd cfg)
            block_bytes += hc * 2 * _sublane8(1) * q_blk * 4
            if block_bytes + temp_bytes <= _VMEM_BUDGET:
                return q_blk, hc
        q_blk //= 2
    return None


def _blocked_bwd_geometry(L, H, D, in_dtype, rate, out_dtype=None,
                          mask_dtype=jnp.int32, interpret=False,
                          seg=False):
    """(q_blk, hc) for the fused q-blocked backward through the autotuner,
    or ``None`` when no configuration is legal (the caller then falls back
    to the XLA-recompute backward)."""
    in_isz = jnp.dtype(in_dtype).itemsize
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else jnp.dtype(in_dtype)

    def analytic():
        return _blocked_bwd_cfg(L, H, D, in_isz, rate,
                                out_itemsize=out_dtype.itemsize, seg=seg)

    cost = _blocked_cost(L, H, D)

    def probe(geom):
        q_blk, hc = geom
        args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 4,
            jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), out_dtype),
            jax.ShapeDtypeStruct((_PROBE_BATCH, L // q_blk, 1, H * q_blk),
                                 jnp.float32),               # lse wire
        ]
        call = _build_blocked_bwd_call(_PROBE_BATCH, L, H, D, in_dtype, rate,
                                       q_blk,
                                       hc, interpret=False, seg=seg)
        ref = analytic()
        return _probe_compiles(
            call, args,
            aggressive=ref is None or cost(geom) < cost(ref),
        )

    return autotune.get().select(
        "blocked_bwd",
        L=L, H=H, D=D, in_dtype=jnp.dtype(in_dtype), out_dtype=out_dtype,
        dropout=rate > 0.0, extra=_seg_extra(mask_dtype, seg),
        candidates=_blocked_candidates(L, H, D), cost=cost, probe=probe,
        analytic=analytic, interpret=interpret, batch=_PROBE_BATCH,
    )


def supports_blocked_bwd(L: int, H: int, D: int, in_itemsize: int,
                         rate: float = 0.0,
                         out_itemsize: int | None = None,
                         in_dtype=None, out_dtype=None,
                         mask_dtype=jnp.int32, segmented=False) -> bool:
    """True when the fused q-blocked backward has a feasible configuration
    for this exact head geometry and input/output itemsizes (no defaults: a
    bert-base answer for a different geometry would be silently wrong). On
    TPU the answer is the autotuner's (compile-probe-validated, cached);
    elsewhere the analytic arithmetic, unchanged. The optional dtypes key
    the probe identically to the execution path's selection. ``segmented``
    keys the block-diagonal (sequence-packing) kernel variant."""
    if L <= _FUSED_BWD_MAX_LEN:
        return False
    return _blocked_bwd_geometry(
        L, H, D,
        _dtype_for_itemsize(in_itemsize, in_dtype),
        rate,
        out_dtype=_dtype_for_itemsize(
            out_itemsize if out_itemsize is not None else in_itemsize,
            out_dtype,
        ),
        mask_dtype=mask_dtype,
        seg=segmented,
    ) is not None


def _build_blocked_bwd_call(B, L, H, D, in_dtype, rate, q_blk, hc,
                            interpret, seg=False):
    """The q-blocked backward ``pallas_call`` for one geometry, shared by
    the execution path and the autotuner's compile probe so they cannot
    drift."""
    spec_q = pl.BlockSpec((1, q_blk, hc * D), lambda b, hj, qi, *_: (b, qi, hj))
    spec_l = pl.BlockSpec((1, L, hc * D), lambda b, hj, qi, *_: (b, 0, hj))

    return pl.pallas_call(
        functools.partial(_blocked_bwd_kernel, scale=1.0 / (D ** 0.5),
                          rate=rate, hc=hc, D=D, seg=seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hc, L // q_blk),
            in_specs=[
                pl.BlockSpec((1, 1, L), lambda b, hj, qi, *_: (b, 0, 0)),  # mask
                spec_q,                                                # q block
                spec_l, spec_l,                                        # k v whole
                spec_q,                                                # g block
                spec_q,                                                # out block
                pl.BlockSpec((1, 1, 1, hc * q_blk),
                             lambda b, hj, qi, *_: (b, qi, 0, hj)),  # lse wire
            ],
            out_specs=[spec_q, spec_l, spec_l],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H * D), in_dtype),     # dq
            jax.ShapeDtypeStruct((B, L, H * D), jnp.float32),  # dk (f32 acc)
            jax.ShapeDtypeStruct((B, L, H * D), jnp.float32),  # dv (f32 acc)
        ],
        interpret=interpret,
        name="flash_bwd",
    )


def _blocked_backward(q, k, v, mask, seed, g, out, lse, q_blk, hc, dtype,
                      rate, interpret: bool, seg: bool = False):
    B, L, H, D = q.shape
    dq, dk, dv = _build_blocked_bwd_call(B, L, H, D, q.dtype, rate, q_blk,
                                         hc, interpret, seg=seg)(
        _row_seeds(seed, B, H), mask[:, None, :], _fold(q), _fold(k), _fold(v),
        _fold(g), _fold(out), _lse_pack(lse, q_blk))
    return (
        dq.reshape(B, L, H, D),
        dk.reshape(B, L, H, D).astype(k.dtype),
        dv.reshape(B, L, H, D).astype(v.dtype),
    )


def _xla_reference(q, k, v, mask, dtype, seg=False):
    """Einsum attention used for the long-sequence backward — the
    dispatcher's XLA path itself, so kernel and fallback cannot drift.
    ``seg=True`` interprets ``mask`` as the segment-id plane and applies
    the block-diagonal permission grid."""
    from .attention import _xla_attention

    return _xla_attention(
        q, k, v, None if seg else mask, dtype=dtype,
        segment_ids=mask if seg else None,
    ).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
@jax.named_scope("flash_fwd")
def _flash_core(q, k, v, mask, seed, dtype, rate, interpret, seg):
    B, L, H, D = q.shape
    if supports_fused_bwd(L, interpret):
        return _flash_forward(q, k, v, mask, seed, dtype, rate, interpret,
                              seg=seg)
    cfg = _blocked_fwd_geometry(
        L, H, D, q.dtype, jnp.dtype(dtype), rate, mask_dtype=mask.dtype,
        interpret=interpret, seg=seg,
    )
    if cfg is None:
        raise ValueError(
            f"no VMEM-feasible blocked-forward config for L={L}, H={H}, "
            f"D={D} (rate={rate}); route this shape to the XLA path "
            f"(supports_blocked_fwd is the dispatcher's gate)"
        )
    return _blocked_forward(q, k, v, mask, seed, *cfg, dtype, rate, interpret,
                            seg=seg)


@jax.named_scope("flash_fwd")
def _fwd(q, k, v, mask, seed, dtype, rate, interpret, seg):
    B, L, H, D = q.shape
    if supports_fused_bwd(L, interpret):
        # the forward also emits per-row logsumexp so the backward skips
        # the max/sum/divide normalization sweeps; the output itself is a
        # residual too (delta identity row term) — XLA already keeps it
        # alive for the output projection's weight grad, so this adds no
        # HBM-resident tensor
        out, lse = _flash_forward(
            q, k, v, mask, seed, dtype, rate, interpret, want_lse=True,
            seg=seg,
        )
        return out, (q, k, v, mask, seed, out, lse)
    if L > _FUSED_BWD_MAX_LEN and _blocked_bwd_geometry(
        L, H, D, q.dtype, rate, out_dtype=jnp.dtype(dtype),
        mask_dtype=mask.dtype, interpret=interpret, seg=seg,
    ) is not None:
        cfg = _blocked_fwd_geometry(
            L, H, D, q.dtype, jnp.dtype(dtype), rate, mask_dtype=mask.dtype,
            interpret=interpret, seg=seg,
        )
        if cfg is not None:
            out, lse = _blocked_forward(
                q, k, v, mask, seed, *cfg, dtype, rate, interpret,
                want_lse=True, seg=seg,
            )
            return out, (q, k, v, mask, seed, out, lse)
    out = _flash_core(q, k, v, mask, seed, dtype, rate, interpret, seg)
    return out, (q, k, v, mask, seed, None, None)


@jax.named_scope("flash_bwd")
def _bwd(dtype, rate, interpret, seg, residuals, g):
    q, k, v, mask, seed, out, lse = residuals
    L, H, D = q.shape[1], q.shape[2], q.shape[3]
    if supports_fused_bwd(L, interpret):
        dq, dk, dv = _flash_backward(
            q, k, v, mask, seed, g.astype(q.dtype), out, lse, dtype, rate,
            interpret, seg=seg,
        )
        return dq, dk, dv, None, None
    if L > _FUSED_BWD_MAX_LEN and lse is not None:
        cfg = _blocked_bwd_geometry(
            L, H, D, q.dtype, rate, out_dtype=jnp.dtype(dtype),
            mask_dtype=mask.dtype, interpret=interpret, seg=seg,
        )
        if cfg is not None:
            dq, dk, dv = _blocked_backward(
                q, k, v, mask, seed, g.astype(q.dtype), out, lse, *cfg,
                dtype, rate, interpret, seg=seg,
            )
            return dq, dk, dv, None, None
    if rate > 0.0:
        # The forward applied the in-kernel dropout mask; an XLA-recompute
        # backward cannot reproduce it. The dispatcher gates dropout on
        # supports_blocked_bwd, so this is unreachable through it.
        raise ValueError(
            f"no VMEM-feasible blocked-backward config for L={L}, H={H}, "
            f"D={D} with dropout; gate on supports_blocked_bwd"
        )
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _xla_reference(q_, k_, v_, mask, dtype, seg=seg),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_flash_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, mask, seed=None, dtype=jnp.float32, rate=0.0,
                    interpret=False, segmented=False):
    """Fused attention over [B, L, H, D] with a [B, L] key-validity mask.

    ``seed``: int32 array of shape (1,) keying the in-kernel dropout mask
    (ignored when ``rate == 0``); internally expanded to a per-batch-row
    seed vector (``_row_seeds``) so batch-sharded executions hand each
    data-parallel shard its rows' global mask streams — a [B] vector may
    also be passed directly. ``rate``: attention-probs dropout rate —
    supported by the fully-fused regime (L <= 512) and by the q-blocked
    regime when BOTH directions have a VMEM-feasible config
    (``supports_blocked_fwd``/``supports_blocked_bwd``); raises ValueError
    for shapes with no feasible kernel config (the dispatcher in
    ops/attention.py gates on the ``supports_*`` predicates and routes such
    shapes to the XLA path instead).

    ``segmented=True`` switches to the sequence-packing contract: ``mask``
    then carries per-token SEGMENT IDS (int32, 0 = pad, 1..S = packed
    segment) and every kernel regime applies the block-diagonal permission
    grid ``q_seg == k_seg != 0`` instead of the key-only 1-D mask; the
    dropout hash keys by absolute (row, col) indices either way, so the
    backward regenerates the exact forward mask.
    """
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), dtype=jnp.int32)
    return _flash_core(q, k, v, mask, seed, dtype, rate, interpret,
                       bool(segmented))

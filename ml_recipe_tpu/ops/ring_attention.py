"""Ring attention — sequence/context parallelism over the mesh ``seq`` axis.

No reference counterpart: the reference handles long documents purely by
data-level chunking (SURVEY.md §2.3 — sliding windows at
split_dataset.py:287-306). This op is the attention-level scale-out the TPU
framework adds: the sequence dimension is sharded over the ``seq`` mesh axis,
each device holds its local Q/K/V slice, and K/V blocks rotate around the
ring via ``ppermute`` while an online-softmax accumulator builds the exact
global attention — memory per device is O(L_local · L_local) instead of
O(L · L), and the K/V transfers ride the ICI ring concurrently with compute.

Algorithm: blockwise attention with running (max, denom, out) renormalisation
(Liu et al., "Ring Attention with Blockwise Transformers", arXiv 2310.01889 —
see PAPERS.md; implementation is original, written against the math).

The BACKWARD is a custom VJP that recomputes each block's probabilities from
the saved per-row logsumexp and rotates (k, v, dk, dv) together around the
ring, so dk/dv partials arrive home after a full loop. Residuals are
O(L_local) per device (q, k, v, out, lse) — plain autodiff through the ring
loop would instead save every step's [B, H, L_loc, L_loc] probability block
plus rotated K/V copies, i.e. O(L_loc · L) per device, forfeiting exactly
the memory saving ring attention exists for (round-2 VERDICT missing #2).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.compat import shard_map

_NEG_INF = -1e30


def _dropout_ids(q_shape, *, axis_name: str, batch_axis: Optional[str], seed):
    """Global-index ingredients for the in-flight attention-probs dropout.

    Keep-bits come from the shared :func:`ops.flash_attention.hash_uniform`
    finalizer keyed by the GLOBAL (batch, head, row, col) index — each
    rotating K/V block's global column offset is derived from the ring step,
    so the mask is independent of how many shards the sequence is split
    over, and identical whether computed here or in a single-device kernel.
    """
    B, L_loc, H, _ = q_shape
    my_idx = jax.lax.axis_index(axis_name)
    seed_val = seed[0].astype(jnp.int32)
    if batch_axis is not None:
        # decorrelate data-parallel groups: their local batch indices
        # overlap, so fold the dp coordinate into the seed
        seed_val = seed_val + jax.lax.axis_index(batch_axis) * jnp.int32(
            -1640531527
        )
    bh = (
        jnp.arange(B, dtype=jnp.int32)[:, None] * jnp.int32(H)
        + jnp.arange(H, dtype=jnp.int32)[None, :]
    )  # [B, H]
    row_ids = my_idx * L_loc + jnp.arange(L_loc, dtype=jnp.int32)
    return seed_val, bh, row_ids


def _make_keep_block(q_shape, *, axis_name: str, batch_axis: Optional[str],
                     seed, rate: float, n_shards):
    """``keep_block(step) -> [B, H, L_loc, L_loc]`` keep-bits for the block
    held at ring step ``step`` (it originated at shard (my_idx - step) mod
    n_shards). Recomputed identically by forward and backward."""
    from .flash_attention import hash_uniform

    _, L_loc, _, _ = q_shape
    L_total = n_shards * L_loc
    my_idx = jax.lax.axis_index(axis_name)
    seed_val, bh, row_ids = _dropout_ids(
        q_shape, axis_name=axis_name, batch_axis=batch_axis, seed=seed
    )

    def keep_block(step):
        col_off = ((my_idx - step) % n_shards) * L_loc
        col_ids = col_off + jnp.arange(L_loc, dtype=jnp.int32)
        x = row_ids[:, None] * jnp.int32(L_total) + col_ids[None, :]
        x = x[None, None, :, :] ^ (
            seed_val + bh[:, :, None, None] * jnp.int32(-1640531527)
        )
        return hash_uniform(x) >= rate

    return keep_block


def _fwd_local(q, k, v, mask, seed, *, axis_name: str, scale: float,
               rate: float = 0.0, batch_axis: Optional[str] = None):
    """Per-shard forward (runs under shard_map).

    q/k/v: [B, L_loc, H, D] local slices; mask: [B, L_loc] key validity.
    Returns ``(out, lse)``: the exact softmax(QK^T)V rows for local Q
    against the FULL global K/V, and the per-row logsumexp [B, H, L_loc]
    the backward recomputes block probabilities from.

    Attention-probs dropout (``rate > 0``): matching torch semantics, the
    softmax DENOMINATOR is undropped; only the value-weighting probs are
    masked and inverse-scaled.
    """
    n_shards = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    B, L_loc, H, D = q.shape

    if rate > 0.0:
        keep_block = _make_keep_block(
            q.shape, axis_name=axis_name, batch_axis=batch_axis,
            seed=seed, rate=rate, n_shards=n_shards,
        )

    def block_scores(k_blk, mask_blk):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
        return jnp.where(mask_blk[:, None, None, :] > 0, s, _NEG_INF)

    def accumulate(carry, k_cur, v_cur, mask_cur, step):
        o_acc, m_acc, l_acc = carry

        s = block_scores(k_cur, mask_cur)                      # [B,H,Lq,Lk]
        m_blk = jnp.max(s, axis=-1)                            # [B,H,Lq]
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(s - m_new[..., None])                      # [B,H,Lq,Lk]
        corr = jnp.exp(m_acc - m_new)                          # [B,H,Lq]

        # the denominator accumulates UNdropped p (torch applies dropout
        # after softmax); only the value weighting is masked
        l_new = l_acc * corr + jnp.sum(p, axis=-1)
        if rate > 0.0:
            p_v = jnp.where(keep_block(step), p * (1.0 / (1.0 - rate)), 0.0)
        else:
            p_v = p
        o_blk = jnp.einsum("bhqk,bkhd->bqhd", p_v.astype(v_cur.dtype), v_cur)
        o_new = o_acc * corr.transpose(0, 2, 1)[..., None] + o_blk.astype(jnp.float32)
        return o_new, m_new, l_new

    def body(i, carry):
        acc, k_cur, v_cur, mask_cur = carry
        acc = accumulate(acc, k_cur, v_cur, mask_cur, i)
        # rotate K/V/mask one step around the ring (ICI neighbour copy,
        # overlapped with the next block's compute by the scheduler)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        return acc, k_nxt, v_nxt, mask_nxt

    o0 = jnp.zeros((B, L_loc, H, D), jnp.float32)
    m0 = jnp.full((B, H, L_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, L_loc), jnp.float32)

    # first n_shards-1 blocks rotate after accumulating; the final block
    # accumulates only — no wasted trailing ring transfer
    acc, k_last, v_last, mask_last = jax.lax.fori_loop(
        0, n_shards - 1, body, ((o0, m0, l0), k, v, mask)
    )
    o, m, l = accumulate(acc, k_last, v_last, mask_last, n_shards - 1)

    l_safe = jnp.maximum(l, 1e-30)
    denom = l_safe.transpose(0, 2, 1)[..., None]               # [B,Lq,H,1]
    lse = m + jnp.log(l_safe)                                  # [B,H,Lq]
    return (o / denom).astype(q.dtype), lse


def _bwd_local(q, k, v, mask, seed, out, lse, do, *, axis_name: str,
               scale: float, rate: float = 0.0,
               batch_axis: Optional[str] = None):
    """Per-shard blockwise-recompute backward (runs under shard_map).

    Each device owns its local Q rows (with ``do``/``out``/``lse`` local)
    and its local K/V columns. Per ring step: recompute the block's exact
    probabilities ``p = exp(s - lse)``, accumulate ``dq`` locally, add this
    device's contribution to the visiting block's ``dk``/``dv``, then rotate
    (k, v, mask, dk, dv) one hop — after a full loop every dk/dv partial is
    back at its owner. Nothing per-step is saved: peak extra memory is one
    [B, H, L_loc, L_loc] scratch block regardless of ring size.
    """
    n_shards = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    if rate > 0.0:
        keep_block = _make_keep_block(
            q.shape, axis_name=axis_name, batch_axis=batch_axis,
            seed=seed, rate=rate, n_shards=n_shards,
        )
        inv_keep = 1.0 / (1.0 - rate)

    do_f = do.astype(jnp.float32)
    out_f = out.astype(jnp.float32)
    # D_i = sum_j P~_ij (dO_i . v_j) = dO_i . out_i (holds WITH dropout:
    # P_ij * keep_ij/(1-rate) is exactly the value-weighting P~_ij)
    D = jnp.einsum("bqhd,bqhd->bhq", do_f, out_f)              # [B,H,Lq]

    def block_grads(i, k_cur, v_cur, mask_cur):
        """(dq_blk, dk_blk, dv_blk) for the block held at ring step ``i``."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur).astype(jnp.float32) * scale
        s = jnp.where(mask_cur[:, None, None, :] > 0, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                        # [B,H,Lq,Lk]

        if rate > 0.0:
            keep = keep_block(i)
            p_v = jnp.where(keep, p * inv_keep, 0.0)
        else:
            p_v = p

        # dV_blk = P~^T dO ; dP~ = dO V^T ; dP = drop'(dP~)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p_v, do_f)
        dp_v = jnp.einsum("bqhd,bkhd->bhqk", do_f, v_cur.astype(jnp.float32))
        if rate > 0.0:
            dp = jnp.where(keep, dp_v * inv_keep, 0.0)
        else:
            dp = dp_v

        # softmax backward: ds = P (dP - D)
        ds = p * (dp - D[..., None])                           # [B,H,Lq,Lk]
        dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds, k_cur.astype(jnp.float32))
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
        return dq_blk * scale, dk_blk * scale, dv_blk

    def body(i, carry):
        dq_acc, k_cur, v_cur, mask_cur, dk_acc, dv_acc = carry
        dq_blk, dk_blk, dv_blk = block_grads(i, k_cur, v_cur, mask_cur)
        dq_acc = dq_acc + dq_blk
        dk_acc = dk_acc + dk_blk
        dv_acc = dv_acc + dv_blk

        # rotate the block AND its gradient partials together; after
        # n_shards hops each dk/dv block is home with every contribution
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_acc, axis_name, perm)
        return dq_acc, k_nxt, v_nxt, mask_nxt, dk_nxt, dv_nxt

    B, L_loc, H, Dh = q.shape
    zeros = lambda: jnp.zeros((B, L_loc, H, Dh), jnp.float32)  # noqa: E731
    # last step peeled (like the forward): the final k/v/mask rotation would
    # feed no further compute — only dk/dv still need their homeward hop
    dq, k_last, v_last, mask_last, dk, dv = jax.lax.fori_loop(
        0, n_shards - 1, body, (zeros(), k, v, mask, zeros(), zeros())
    )
    dq_blk, dk_blk, dv_blk = block_grads(n_shards - 1, k_last, v_last, mask_last)
    dq = dq + dq_blk
    dk = jax.lax.ppermute(dk + dk_blk, axis_name, perm)
    dv = jax.lax.ppermute(dv + dv_blk, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _stream_row_seeds(seed, *, B: int, H: int, dp_size: int):
    """GLOBAL per-row dropout seeds for the composed inner, [B] int32.

    Built OUTSIDE the shard_map and sharded over ``batch_axis`` so the
    composed path never calls ``axis_index`` — XLA's constant sinking
    clones a ``partition-id``-derived pallas operand into while-loop
    bodies, where the SPMD partitioner rejects it (the dense inner's
    in-shard fold never feeds a pallas call, so it is unaffected).

    Bit-compatible with the dense ring AND the single-chip streaming
    kernels: row ``b`` of dp group ``r`` gets ``seed + r*PRIME +
    b_local*H*PRIME`` — exactly ``_row_seeds`` applied to the dense
    path's dp-folded seed (the kernel adds the per-head ``h*PRIME``)."""
    prime = jnp.int32(-1640531527)
    rows = jnp.arange(B, dtype=jnp.int32)
    b_loc = B // dp_size
    return (
        seed[0].astype(jnp.int32)
        + (rows // b_loc) * prime
        + (rows % b_loc) * jnp.int32(H) * prime
    )


def _merge_hop(o_acc, lse_acc, out_hop, lse_hop):
    """Fold one hop's normalized streaming output into the running global
    accumulator. Each hop's kernel returns ``out_hop = N_hop / l_hop`` and
    ``lse_hop = log(sum_k e^s)`` over the visiting block only, so

        out_global = sum_hop out_hop * exp(lse_hop - lse_global)

    with ``lse_global = logaddexp over hops`` — exact online-softmax
    across the ``ppermute`` rotation (the within-hop sweep already merged
    inside the kernel). Holds verbatim under torch-semantics dropout: the
    undropped denominator is exactly what ``lse`` carries. An all-masked
    hop arrives with ``lse_hop`` ~ -1e30 and merges with weight zero."""
    lse_new = jnp.logaddexp(lse_acc, lse_hop)                  # [B,H,Lq]
    w_acc = jnp.exp(lse_acc - lse_new).transpose(0, 2, 1)[..., None]
    w_hop = jnp.exp(lse_hop - lse_new).transpose(0, 2, 1)[..., None]
    return o_acc * w_acc + out_hop.astype(jnp.float32) * w_hop, lse_new


def _stream_fwd_local(q, k, v, mask, seed, spos, *, axis_name: str,
                      rate: float, batch_axis: Optional[str],
                      blk: int, hc: int, interpret: bool, seg: bool):
    """Composed streaming-ring forward (runs under shard_map).

    Per hop the visiting K/V shard is consumed by the streaming Pallas
    forward — per-device activation scratch is O(blk^2) per program
    instead of the dense inner's O(L_loc^2) block — and the online-softmax
    state carries across hops via ``_merge_hop``. Dropout keep-bits are
    keyed by ABSOLUTE (row, col) against the GLOBAL length, bit-identical
    to the dense ring inner and to a single-chip streaming kernel.

    ``seed``: per-row [B] seeds (``_stream_row_seeds``, dp fold baked in).
    ``spos``: this shard's [L_loc] slice of the global position iota —
    ``spos[0]`` is the absolute q-row base, and a copy of it ROTATES with
    the K/V block (each visiting block carries its own absolute column
    offset home), so no ``axis_index`` value ever feeds the kernels.

    ``seg``: ``mask`` carries segment ids; the q-side ids stay resident
    while the k-side copy rotates, concatenated per hop into the
    ``seg_split`` kernel operand. Unsegmented, ``mask`` is the rotating
    key-validity row.
    """
    from .flash_streaming import _stream_forward

    n_shards = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    B, L_loc, H, D = q.shape
    row_base = spos[:1].astype(jnp.int32)

    def hop(k_cur, v_cur, mask_cur, col_base):
        mask_arg = (
            jnp.concatenate([mask, mask_cur], axis=1) if seg else mask_cur
        )
        return _stream_forward(
            q, k_cur, v_cur, mask_arg, seed, blk, hc, jnp.float32,
            rate, interpret, seg=seg,
            base=jnp.concatenate([row_base, col_base]),
            L_hash=n_shards * L_loc, seg_split=seg,
        )

    def body(i, carry):
        o_acc, lse_acc, k_cur, v_cur, mask_cur, col_cur = carry
        out_hop, lse_hop = hop(k_cur, v_cur, mask_cur, col_cur)
        o_acc, lse_acc = _merge_hop(o_acc, lse_acc, out_hop, lse_hop)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        col_nxt = jax.lax.ppermute(col_cur, axis_name, perm)
        return o_acc, lse_acc, k_nxt, v_nxt, mask_nxt, col_nxt

    o0 = jnp.zeros((B, L_loc, H, D), jnp.float32)
    lse0 = jnp.full((B, H, L_loc), _NEG_INF, jnp.float32)
    o, lse, k_last, v_last, mask_last, col_last = jax.lax.fori_loop(
        0, n_shards - 1, body, (o0, lse0, k, v, mask, row_base)
    )
    out_hop, lse_hop = hop(k_last, v_last, mask_last, col_last)
    o, lse = _merge_hop(o, lse, out_hop, lse_hop)
    return o.astype(q.dtype), lse


def _stream_bwd_local(q, k, v, mask, seed, spos, out, lse, do, *,
                      axis_name: str, rate: float,
                      batch_axis: Optional[str],
                      blk: int, hc: int, interpret: bool, seg: bool):
    """Composed streaming-ring backward (runs under shard_map).

    The GLOBAL per-row ``lse`` (and global-normalized ``out``) saved by the
    forward let every hop recompute its block's exact probabilities
    ``p = exp(s - lse)`` inside the streaming dq/dk/dv kernels — no
    per-hop renormalisation chain. ``dq`` sums over hops locally in f32;
    ``dk``/``dv`` partials accumulate in a carry that rotates home with
    the visiting block (last hop peeled, one final homeward ``ppermute``,
    exactly the dense inner's schedule). ``seed``/``spos`` as in
    ``_stream_fwd_local``: per-row seeds and the sharded position iota,
    with the column base rotating alongside the visiting block."""
    from .flash_streaming import _stream_backward

    n_shards = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    B, L_loc, H, D = q.shape
    row_base = spos[:1].astype(jnp.int32)

    def hop_grads(k_cur, v_cur, mask_cur, col_base):
        mask_arg = (
            jnp.concatenate([mask, mask_cur], axis=1) if seg else mask_cur
        )
        return _stream_backward(
            q, k_cur, v_cur, mask_arg, seed, do, out, lse, blk, hc,
            jnp.float32, rate, interpret, seg=seg,
            base=jnp.concatenate([row_base, col_base]),
            L_hash=n_shards * L_loc, seg_split=seg,
        )

    def body(i, carry):
        dq_acc, k_cur, v_cur, mask_cur, col_cur, dk_acc, dv_acc = carry
        dq_h, dk_h, dv_h = hop_grads(k_cur, v_cur, mask_cur, col_cur)
        dq_acc = dq_acc + dq_h.astype(jnp.float32)
        dk_acc = dk_acc + dk_h.astype(jnp.float32)
        dv_acc = dv_acc + dv_h.astype(jnp.float32)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        col_nxt = jax.lax.ppermute(col_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_acc, axis_name, perm)
        return dq_acc, k_nxt, v_nxt, mask_nxt, col_nxt, dk_nxt, dv_nxt

    zeros = lambda: jnp.zeros((B, L_loc, H, D), jnp.float32)  # noqa: E731
    dq, k_last, v_last, mask_last, col_last, dk, dv = jax.lax.fori_loop(
        0, n_shards - 1, body,
        (zeros(), k, v, mask, row_base, zeros(), zeros()),
    )
    dq_h, dk_h, dv_h = hop_grads(k_last, v_last, mask_last, col_last)
    dq = dq + dq_h.astype(jnp.float32)
    dk = jax.lax.ppermute(dk + dk_h.astype(jnp.float32), axis_name, perm)
    dv = jax.lax.ppermute(dv + dv_h.astype(jnp.float32), axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def ring_stream_geometry(L_loc: int, H: int, D: int, dtype, rate: float,
                         *, segmented: bool = False,
                         interpret: bool = False):
    """(blk, hc) for the composed streaming-ring inner at LOCAL length
    ``L_loc``, or None when no legal streaming geometry exists (the caller
    falls back to the dense inner). Keys the autotune cache with the
    ``-ring`` suffix so single-chip picks are never reused."""
    from .flash_streaming import _streaming_geometry

    return _streaming_geometry(
        L_loc, H, D, jnp.dtype(dtype), jnp.dtype(jnp.float32), rate,
        mask_dtype=jnp.int32, interpret=interpret,
        seg=segmented, ring=True,
    )


def ring_attention(
    q,
    k,
    v,
    mask=None,
    *,
    mesh: Mesh,
    axis_name: str = "seq",
    batch_axis: Optional[str] = None,
    dtype=jnp.float32,
    rate: float = 0.0,
    seed=None,
    custom_backward: bool = True,
    segment_ids=None,
    inner: str = "auto",
    interpret: bool = False,
):
    """Exact global attention with Q/K/V sharded over ``axis_name``.

    Inputs are GLOBAL [B, L, H, D] arrays (sharded or not — shard_map
    partitions them); output is the global [B, L, H, D] attention result,
    sequence-sharded the same way. ``batch_axis`` names the mesh axis the
    batch dim is data-parallel over (composes dp x sp inside one jitted
    step); None replicates over any remaining axes.

    ``rate``/``seed``: attention-probs dropout applied in-flight during the
    ring sweep; the keep-mask is keyed by global indices, so results are
    invariant to the number of sequence shards.

    ``custom_backward``: use the blockwise-recompute VJP (O(L_local)
    residuals). False falls back to plain autodiff through the ring loop —
    kept as the differential-testing oracle (it stores every ring step's
    probability block: correct, but O(L_local · L) memory).

    ``inner``: 'auto' consumes each visiting K/V shard through the
    streaming Pallas kernels when a legal (blk, hc) geometry exists at the
    local length (per-device activation scratch O(blk^2) instead of the
    dense inner's O(L_loc^2)), falling back to the dense inner otherwise.
    'stream' requires the composed path (raises without a geometry);
    'dense' forces the historical inner. Results are identical up to f32
    reduction reordering — dropout masks bit-identical — across inners.

    ``segment_ids``: optional [B, L] packed-segment ids (0 = pad); needs
    the composed streaming inner (the dense inner is unsegmented).
    ``interpret``: run the streaming kernels in Pallas interpret mode
    (forced automatically off-TPU).
    """
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), dtype=jnp.int32)

    seg = segment_ids is not None
    B, L, H, D = q.shape
    n_shards = int(mesh.shape[axis_name])
    scale = 1.0 / (D ** 0.5)

    stream_cfg = None
    if inner in ("auto", "stream") and custom_backward and L % n_shards == 0:
        if not interpret and jax.default_backend() != "tpu":
            import logging

            logging.getLogger(__name__).warning(
                "ring attention on a %s backend: the composed streaming "
                "inner runs in Pallas INTERPRET mode (a correctness "
                "vehicle, far slower than a compiled kernel).",
                jax.default_backend(),
            )
            interpret = True
        stream_cfg = ring_stream_geometry(
            L // n_shards, H, D, dtype, rate, segmented=seg,
            interpret=interpret,
        )
    if stream_cfg is None:
        if inner == "stream":
            raise ValueError(
                f"no legal streaming geometry for the composed ring inner "
                f"at L_loc={L // n_shards}, H={H}, D={D} (rate={rate}); "
                f"use inner='dense' or a longer sequence"
            )
        if seg:
            raise NotImplementedError(
                "segment_ids require the composed streaming-ring inner "
                "(no legal geometry at this shape, or inner='dense'/"
                "custom_backward=False was forced); the dense ring inner "
                "is unsegmented"
            )

    seq_spec = P(batch_axis, axis_name, None, None)
    mask_spec = P(batch_axis, axis_name)
    lse_spec = P(batch_axis, None, axis_name)

    # The composed inner never calls ``axis_index``: the per-row dropout
    # seeds fold the dp rank OUTSIDE the shard_map (sharding the [B] row
    # over ``batch_axis`` hands each dp group exactly the dense path's
    # in-shard fold), and absolute (row, col) bases come from a sharded
    # position iota whose column copy ppermutes with the visiting K/V
    # block. XLA's constant sinking clones ``partition-id``-derived pallas
    # operands into while-loop bodies, where the SPMD partitioner rejects
    # them — so no kernel operand may depend on it.
    if stream_cfg is not None:
        blk, hc = stream_cfg
        common = dict(axis_name=axis_name, rate=rate, batch_axis=batch_axis,
                      blk=blk, hc=hc, interpret=interpret, seg=seg)
        mask = (
            jnp.where(mask > 0, segment_ids.astype(jnp.int32), 0)
            if seg else mask
        )
        local_fwd, local_bwd = _stream_fwd_local, _stream_bwd_local
        dp_size = int(mesh.shape[batch_axis]) if batch_axis is not None else 1
        seed_arg = _stream_row_seeds(seed, B=B, H=H, dp_size=dp_size)
        seed_spec = P(batch_axis)
    else:
        common = dict(axis_name=axis_name, scale=scale, rate=rate,
                      batch_axis=batch_axis)

        def local_fwd(q_, k_, v_, mask_, seed_, spos_, **kw):
            return _fwd_local(q_, k_, v_, mask_, seed_, **kw)

        def local_bwd(q_, k_, v_, mask_, seed_, spos_, out_, lse_, do_,
                      **kw):
            return _bwd_local(q_, k_, v_, mask_, seed_, out_, lse_, do_,
                              **kw)

        seed_arg, seed_spec = seed, P(None)

    spos = jnp.arange(L, dtype=jnp.int32)
    spos_spec = P(axis_name)

    fwd_sm = shard_map(
        functools.partial(local_fwd, **common),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, mask_spec, seed_spec,
                  spos_spec),
        out_specs=(seq_spec, lse_spec),
        check_vma=False,
    )

    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)

    if not custom_backward:
        return fwd_sm(q, k, v, mask, seed_arg, spos)[0]

    bwd_sm = shard_map(
        functools.partial(local_bwd, **common),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, mask_spec, seed_spec,
                  spos_spec, seq_spec, lse_spec, seq_spec),
        out_specs=(seq_spec, seq_spec, seq_spec),
        check_vma=False,
    )

    @jax.custom_vjp
    def attn(q_, k_, v_, mask_, seed_, spos_):
        return fwd_sm(q_, k_, v_, mask_, seed_, spos_)[0]

    def attn_fwd(q_, k_, v_, mask_, seed_, spos_):
        out, lse = fwd_sm(q_, k_, v_, mask_, seed_, spos_)
        return out, (q_, k_, v_, mask_, seed_, spos_, out, lse)

    def attn_bwd(res, do):
        q_, k_, v_, mask_, seed_, spos_, out, lse = res
        dq, dk, dv = bwd_sm(q_, k_, v_, mask_, seed_, spos_, out, lse, do)
        return dq, dk, dv, None, None, None

    attn.defvjp(attn_fwd, attn_bwd)
    return attn(q, k, v, mask, seed_arg, spos)

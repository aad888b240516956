"""Streaming-KV flash attention: the long-sequence regime beyond ~2k.

The q-blocked kernels (ops/flash_attention.py) keep each head-group's WHOLE
K/V resident in VMEM, which caps them at L ~= 2048 for bf16/D=64 — beyond
that the dispatcher fell back to XLA attention, which materializes the
[B, H, L, L] score tensor in HBM (805 MB per bert-base head-set at L=4096).
This module removes that single-chip ceiling with the classic
FlashAttention-2 tiling: K/V stream through VMEM in blocks, the forward
keeps an online-softmax state (running max / denominator / output
accumulator) in VMEM scratch across the k sweep, and the backward splits
into a dq kernel (k innermost, dq accumulated in f32 scratch) and a dk/dv
kernel (q innermost, dk/dv accumulated in f32 scratch) — the [L, L] tensor
never exists in HBM in either direction, and per-program VMEM is O(blk^2),
independent of L.

Everything that made the resident-KV kernels correct is reused unchanged:
the folded [B, L, H*D] layout (no relayout copies), per-batch-row seed
prefetch, the forward-saved per-row logsumexp (probabilities recomputed as
one ``exp(s - lse)``), the FlashAttention-2 delta identity for the softmax
row term (``row_i = g_i . out_i``), and the murmur3-hash dropout keyed by
ABSOLUTE (row, col) indices — so a streaming backward regenerates the
streaming forward's exact mask, and the mask for a given (seed, L) is
bit-identical to what the fused/q-blocked kernels would draw.

Replaces the long-context portion of the reference's HF BERT CUDA
attention (SURVEY.md §2.2); the reference itself has no >2k story at all —
its max_seq_len is 512 (config/test_bert.cfg:66).

Dispatcher position (ops/attention.py): AFTER the proven fused/q-blocked
regimes (whose on-chip numbers are recorded), BEFORE the XLA fallback —
it only activates where XLA was the previous answer, so it is pure upside;
the on-chip A/B is staged in the runbook like every other unproven lever.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune
from .flash_attention import (
    _NEG_INF,
    _PROBE_BATCH,
    _VMEM_BUDGET,
    _allowed_grid,
    _dtype_for_itemsize,
    _fold,
    _legal_head_chunks,
    _lse_pack,
    _lse_unpack,
    _probe_compiles,
    _row_seeds,
    _seg_extra,
    _sublane8,
    _uniform_grid,
)


def _pick_stream_block(L: int):
    for blk in (512, 256, 128):
        if L % blk == 0 and L // blk >= 2:
            return blk
    return None


def streaming_cfg(L: int, H: int, D: int, in_itemsize: int,
                  out_itemsize: int, rate: float = 0.0, seg: bool = False):
    """(blk, hc) for the streaming kernels, or ``None``.

    Working set per program (the dk/dv kernel is the heaviest): f32
    [blk, blk] tiles — p, dp, ds + one of deliberate margin (+ the dropout
    uniform tile when ``rate > 0``; no compile probe here, so the paper
    arithmetic must not run the budget to the wire); per-stream blocks of
    hc*D lanes double-buffered at their own itemsizes (q, k, v, g, out in;
    dk, dv out) plus the (1, 1, 1, hc*blk) lse wire block; f32
    accumulator scratch (2 x [blk, hc*D] in the dk/dv kernel, 1 + the
    [hc, blk, 1] m/l pair in the forward — scratch is not double-buffered).
    """
    blk = _pick_stream_block(L)
    if blk is None:
        return None
    # + the [blk, blk] block-diagonal permission tile when segment-aware
    n_tiles = 4 + (1 if rate > 0.0 else 0) + (1 if seg else 0)
    tile_bytes = n_tiles * blk * blk * 4
    for hc in sorted(_legal_head_chunks(H, D), reverse=True):
        lanes = hc * D
        # every stream at ITS OWN itemsize (the discipline the blocked-bwd
        # cfg learned in round 4): q/k/v/g in-blocks and the dq|dk+dv
        # out-blocks carry the INPUT dtype; the saved-out residual
        # in-block carries the forward-OUTPUT dtype
        block_bytes = (
            2 * blk * lanes * (4 + 2) * in_itemsize  # q k v g + dk,dv
            + 2 * blk * lanes * out_itemsize         # out residual
            + hc * 2 * _sublane8(1) * blk * 4        # lse wire block
        )
        scratch_bytes = 2 * blk * lanes * 4 + 2 * hc * blk * 128 * 4
        if block_bytes + scratch_bytes + tile_bytes <= _VMEM_BUDGET:
            return blk, hc
    return None


def _stream_candidates(L: int, H: int, D: int):
    """All (blk, hc) candidates of the streaming regime (the autotuner's
    enumeration; ``streaming_cfg`` walks the same space analytically)."""
    blks = [blk for blk in (512, 256, 128) if L % blk == 0 and L // blk >= 2]
    return [(blk, hc) for blk in blks
            for hc in sorted(_legal_head_chunks(H, D), reverse=True)]


def _streaming_geometry(L, H, D, in_dtype, out_dtype, rate,
                        mask_dtype=None, interpret=False, seg=False,
                        ring=False):
    """(blk, hc) for the streaming kernels through the autotuner, or
    ``None``. One geometry serves both directions, so the probe compiles
    the forward AND the heavier dk/dv backward — a candidate is legal only
    when both lower. ``ring`` keys the composed streaming-ring regime
    separately (``-ring`` cache-key suffix): there ``L`` is the LOCAL
    shard length and the kernels carry the extra base/global-hash operands,
    so a cached single-chip pick must never be reused for it (nor vice
    versa)."""
    in_isz = jnp.dtype(in_dtype).itemsize
    out_isz = jnp.dtype(out_dtype).itemsize
    mask_dtype = jnp.dtype(mask_dtype) if mask_dtype is not None else (
        jnp.dtype(jnp.int32)
    )

    def analytic():
        return streaming_cfg(L, H, D, in_isz, out_isz, rate, seg=seg)

    def cost(geom):
        blk, hc = geom
        # k/v re-stream once per q block: HBM traffic and program count both
        # scale with (L/blk); ties break toward larger head chunks
        return ((L // blk) * (H // hc), H // hc)

    def probe(geom):
        blk, hc = geom
        ref = analytic()
        aggressive = ref is None or cost(geom) < cost(ref)
        fwd_args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((2,), jnp.int32),          # [row, col] base
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 3,
        ]
        fwd = _build_stream_fwd_call(_PROBE_BATCH, L, H, D, in_dtype,
                                     out_dtype, rate,
                                     blk, hc, interpret=False, seg=seg)
        fwd_compiled = _probe_compiles(fwd, fwd_args, aggressive=aggressive)
        if not fwd_compiled:
            return False
        dkv_args = [
            jax.ShapeDtypeStruct((_PROBE_BATCH,), jnp.int32),  # row seeds
            jax.ShapeDtypeStruct((2,), jnp.int32),          # [row, col] base
            jax.ShapeDtypeStruct((_PROBE_BATCH, 1, L), mask_dtype),  # mask
            *[jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), in_dtype)] * 4,
            jax.ShapeDtypeStruct((_PROBE_BATCH, L, H * D), out_dtype),
            jax.ShapeDtypeStruct((_PROBE_BATCH, L // blk, 1, H * blk),
                                 jnp.float32),
        ]
        dkv = _build_stream_dkv_call(_PROBE_BATCH, L, H, D, in_dtype, rate,
                                     blk, hc, interpret=False, seg=seg)
        # both legs as ONE rankable result: the autotuner ranks legal
        # candidates by the summed compiled-cost estimate (fwd + dkv)
        return autotune.combine_for_ranking(
            fwd_compiled,
            _probe_compiles(dkv, dkv_args, aggressive=aggressive),
        )

    return autotune.get().select(
        "stream",
        L=L, H=H, D=D, in_dtype=jnp.dtype(in_dtype), out_dtype=out_dtype,
        dropout=rate > 0.0,
        extra=_seg_extra(mask_dtype, seg) + ("-ring" if ring else ""),
        candidates=_stream_candidates(L, H, D), cost=cost, probe=probe,
        analytic=analytic, interpret=interpret, batch=_PROBE_BATCH,
    )


def supports_streaming(L: int, H: int, D: int, in_itemsize: int,
                       out_itemsize: int, rate: float = 0.0,
                       in_dtype=None, out_dtype=None,
                       mask_dtype=None, segmented=False) -> bool:
    """True when the streaming regime applies: a legal block geometry that
    fits VMEM — the autotuner's compile-probe-validated answer on TPU, the
    analytic arithmetic elsewhere. Both directions share one (blk, hc)
    config, so — unlike the q-blocked regime — dropout needs no second
    feasibility check. The optional dtypes key the probe identically to
    the execution path's selection. ``segmented`` keys the block-diagonal
    (sequence-packing) kernel variant."""
    return _streaming_geometry(
        L, H, D,
        _dtype_for_itemsize(in_itemsize, in_dtype),
        _dtype_for_itemsize(out_itemsize, out_dtype),
        rate,
        mask_dtype=mask_dtype,
        seg=segmented,
    ) is not None


def _keep_tile(seed_ref, base_ref, b, bh, L, blk, qi, ki, rate):
    """Dropout keep-bits for one (qi, ki) tile.

    ``base_ref`` is the scalar-prefetch ``[row_base, col_base]`` pair: the
    ABSOLUTE offset of this invocation's q rows / k cols in the global
    sequence. Single-chip calls pass (0, 0) and ``L`` = the local length —
    bit-identical to the historical scheme; the composed streaming-ring
    path passes each hop's shard offsets and ``L`` = the GLOBAL length, so
    the mask a shard draws for a visiting K/V block is exactly the tile a
    single-chip kernel would draw at those absolute coordinates."""
    u = _uniform_grid(
        seed_ref[b], bh, L,
        rows=blk, row_offset=base_ref[0] + qi * blk,
        cols=blk, col_offset=base_ref[1] + ki * blk,
    )
    return u >= rate


def _stream_mask_tile(mask_ref, blk, qi, ki, seg: bool,
                      seg_split: bool = False):
    """The attend-permission tile of one (qi, ki) program.

    Unsegmented: mask_ref is the ``(1, 1, blk)`` k-slice block and the tile
    is the historical key-only ``[1, blk]`` broadcast row. Segmented: the
    mask block is the WHOLE ``(1, 1, L)`` segment-id row (its index map is
    constant in qi/ki) and both the q- and k-slices come from dynamic
    slices of it, giving the ``[blk, blk]`` block-diagonal grid.
    ``seg_split``: the row is ``(1, 1, 2*L)`` with the q-side ids in
    ``[0:L]`` and the k-side ids in ``[L:2L]`` — the composed ring layout,
    where the visiting K/V shard's ids differ from the local q shard's."""
    if seg:
        L_ids = mask_ref.shape[2] // 2 if seg_split else mask_ref.shape[2]
        k_off = L_ids if seg_split else 0
        qm = mask_ref[0, 0, pl.ds(qi * blk, blk)]
        km = mask_ref[0, 0, pl.ds(k_off + ki * blk, blk)]
        return _allowed_grid(qm, km, True)
    return mask_ref[0, 0, :][None, :] > 0


def _stream_fwd_kernel(seed_ref, base_ref, mask_ref, q_ref, k_ref, v_ref,
                       o_ref, lse_ref, acc_ref, m_ref, l_ref,
                       *, scale: float, rate: float, hc: int, D: int,
                       L: int, seg: bool = False, seg_split: bool = False):
    b, hj, qi, ki = (pl.program_id(0), pl.program_id(1),
                     pl.program_id(2), pl.program_id(3))
    nk = pl.num_programs(3)
    blk = q_ref.shape[1]
    allowed = _stream_mask_tile(mask_ref, blk, qi, ki, seg,
                                seg_split=seg_split)
    first = ki == 0
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

        m_old = jnp.where(first, jnp.float32(_NEG_INF), m_ref[h, :, :])
        l_old = jnp.where(first, 0.0, l_ref[h, :, :])
        acc_old = jnp.where(first, 0.0, acc_ref[:, sl])

        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        # a k-block whose keys are ALL masked for rows no valid key has
        # reached yet leaves m at _NEG_INF and contributes e = 1 per key —
        # the first block with a real key then drives alpha = exp(-huge)
        # to zero and wipes that contamination (same end semantics as the
        # resident-KV kernels: rows with no valid key anywhere produce
        # finite garbage that downstream masking ignores)
        alpha = jnp.exp(m_old - m_new)
        e = jnp.exp(s - m_new)                     # [blk, blk] f32
        l_new = alpha * l_old + jnp.sum(e, axis=-1, keepdims=True)

        if rate > 0.0:
            keep = _keep_tile(seed_ref, base_ref, b, hj * hc + h, L, blk,
                              qi, ki, rate)
            e_av = jnp.where(keep, e * (1.0 / (1.0 - rate)), 0.0)
        else:
            e_av = e
        acc_new = alpha * acc_old + jax.lax.dot_general(
            e_av.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        m_ref[h, :, :] = m_new
        l_ref[h, :, :] = l_new
        acc_ref[:, sl] = acc_new

        @pl.when(ki == nk - 1)
        def _finish():
            o_ref[0, :, sl] = (acc_new * (1.0 / l_new)).astype(o_ref.dtype)
            lse_ref[0, 0, 0, h * blk:(h + 1) * blk] = (
                m_new + jnp.log(l_new)
            )[:, 0]  # lane row at the head-major offset (_lse_pack)


def _stream_tile_ds(q, k, v, g, out, lse, allowed, scale, keep, rate,
                    seg: bool = False):
    """Shared [blk, blk] backward tile math: probabilities from the saved
    row lse, dropout regenerated from absolute indices, softmax row term
    from the delta identity. ``allowed`` is the attend-permission tile
    ([1, blk] key-only broadcast or the [blk, blk] block-diagonal grid).
    Returns (p_drop, ds) in f32."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(allowed, s, _NEG_INF)
    p = jnp.exp(s - lse)                           # pre-dropout probs
    if seg:
        # an ALL-masked segmented row (pad query) has lse == -1e30 and
        # exp(s - lse) degenerates to 1 on forbidden keys — zero them so
        # pad-row garbage never leaks into real dk/dv (healthy rows are
        # already 0 there; see flash_attention._attention_bwd_math)
        p = jnp.where(allowed, p, 0.0)
    dp_drop = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if keep is not None:
        inv = jnp.float32(1.0 / (1.0 - rate))
        p_drop = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp_drop * inv, 0.0)
    else:
        p_drop = p
        dp = dp_drop
    row = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    ds = p * (dp - row)
    return p_drop, ds


def _stream_dq_kernel(seed_ref, base_ref, mask_ref, q_ref, k_ref, v_ref,
                      g_ref, out_ref, lse_ref, dq_ref, dqa_ref,
                      *, scale: float, rate: float, hc: int, D: int,
                      L: int, seg: bool = False, seg_split: bool = False):
    b, hj, qi, ki = (pl.program_id(0), pl.program_id(1),
                     pl.program_id(2), pl.program_id(3))
    nk = pl.num_programs(3)
    blk = q_ref.shape[1]
    allowed = _stream_mask_tile(mask_ref, blk, qi, ki, seg,
                                seg_split=seg_split)
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)
        keep = (
            _keep_tile(seed_ref, base_ref, b, hj * hc + h, L, blk, qi, ki,
                       rate)
            if rate > 0.0 else None
        )
        kk = k_ref[0, :, sl]
        _, ds = _stream_tile_ds(
            q_ref[0, :, sl], kk, v_ref[0, :, sl],
            g_ref[0, :, sl], out_ref[0, :, sl],
            lse_ref[0, 0, 0, h * blk:(h + 1) * blk][:, None],
            allowed, scale, keep, rate, seg=seg,
        )
        dq_acc = jnp.where(ki == 0, 0.0, dqa_ref[:, sl]) + jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dqa_ref[:, sl] = dq_acc

        @pl.when(ki == nk - 1)
        def _finish():
            dq_ref[0, :, sl] = (dq_acc * scale).astype(dq_ref.dtype)


def _stream_dkv_kernel(seed_ref, base_ref, mask_ref, k_ref, v_ref, q_ref,
                       g_ref, out_ref, lse_ref, dk_ref, dv_ref, dka_ref,
                       dva_ref, *, scale: float, rate: float, hc: int,
                       D: int, L: int, seg: bool = False,
                       seg_split: bool = False):
    # note the grid: (B, HJ, nk, nq) — q INNERMOST, so the dk/dv scratch
    # accumulates across the whole q sweep while k/v blocks stay resident
    b, hj, ki, qi = (pl.program_id(0), pl.program_id(1),
                     pl.program_id(2), pl.program_id(3))
    nq = pl.num_programs(3)
    blk = k_ref.shape[1]
    allowed = _stream_mask_tile(mask_ref, blk, qi, ki, seg,
                                seg_split=seg_split)
    for h in range(hc):
        sl = slice(h * D, (h + 1) * D)
        keep = (
            _keep_tile(seed_ref, base_ref, b, hj * hc + h, L, blk, qi, ki,
                       rate)
            if rate > 0.0 else None
        )
        q = q_ref[0, :, sl]
        g = g_ref[0, :, sl]
        p_drop, ds = _stream_tile_ds(
            q, k_ref[0, :, sl], v_ref[0, :, sl], g,
            out_ref[0, :, sl],
            lse_ref[0, 0, 0, h * blk:(h + 1) * blk][:, None],
            allowed, scale, keep, rate, seg=seg,
        )
        dv_acc = jnp.where(qi == 0, 0.0, dva_ref[:, sl]) + jax.lax.dot_general(
            p_drop.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc = jnp.where(qi == 0, 0.0, dka_ref[:, sl]) + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dva_ref[:, sl] = dv_acc
        dka_ref[:, sl] = dk_acc

        @pl.when(qi == nq - 1)
        def _finish():
            dk_ref[0, :, sl] = (dk_acc * scale).astype(dk_ref.dtype)
            dv_ref[0, :, sl] = dv_acc.astype(dv_ref.dtype)


def _stream_mask_spec(L, blk, *, k_index, seg: bool, seg_split: bool = False):
    """Mask BlockSpec of the streaming kernels: the historical ``(1, 1,
    blk)`` k-slice, or — segment-aware — the whole ``(1, 1, L)`` id row
    (constant index map, so Pallas keeps it resident; the kernel slices
    both the q and k sides dynamically). ``seg_split`` doubles the row to
    ``(1, 1, 2L)`` — q-side ids then k-side ids, the composed ring
    layout."""
    if seg:
        width = 2 * L if seg_split else L
        return pl.BlockSpec((1, 1, width), lambda b, hj, i, j, *_: (b, 0, 0))
    if k_index == 2:
        return pl.BlockSpec((1, 1, blk), lambda b, hj, ki, qi, *_: (b, 0, ki))
    return pl.BlockSpec((1, 1, blk), lambda b, hj, qi, ki, *_: (b, 0, ki))


def _build_stream_fwd_call(B, L, H, D, in_dtype, out_dtype, rate, blk, hc,
                           interpret, seg=False, L_hash=None,
                           seg_split=False):
    """The streaming forward ``pallas_call`` for one (blk, hc), shared by
    the execution path and the autotuner's compile probe so they cannot
    drift. ``L_hash`` keys the dropout hash (the GLOBAL sequence length in
    the composed ring regime; defaults to ``L``, the local/global length of
    a single-chip call)."""
    spec_q = pl.BlockSpec((1, blk, hc * D), lambda b, hj, qi, ki, *_: (b, qi, hj))
    spec_k = pl.BlockSpec((1, blk, hc * D), lambda b, hj, qi, ki, *_: (b, ki, hj))
    return pl.pallas_call(
        functools.partial(_stream_fwd_kernel, scale=1.0 / (D ** 0.5),
                          rate=rate, hc=hc, D=D,
                          L=L if L_hash is None else L_hash, seg=seg,
                          seg_split=seg_split),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hc, L // blk, L // blk),
            in_specs=[
                _stream_mask_spec(L, blk, k_index=3, seg=seg,
                                  seg_split=seg_split),
                spec_q, spec_k, spec_k,
            ],
            out_specs=[
                spec_q,
                pl.BlockSpec((1, 1, 1, hc * blk),
                             lambda b, hj, qi, ki, *_: (b, qi, 0, hj)),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, hc * D), jnp.float32),   # acc
                pltpu.VMEM((hc, blk, 1), jnp.float32),    # running max
                pltpu.VMEM((hc, blk, 1), jnp.float32),    # running denom
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H * D), out_dtype),
            jax.ShapeDtypeStruct((B, L // blk, 1, H * blk), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )


def _zero_base():
    """The single-chip ``[row_base, col_base]`` scalar-prefetch operand:
    absolute offsets (0, 0) — the historical hash, bit-for-bit."""
    return jnp.zeros((2,), dtype=jnp.int32)


def _stream_forward(q, k, v, mask, seed, blk, hc, dtype, rate, interpret,
                    seg=False, base=None, L_hash=None, seg_split=False):
    B, L, H, D = q.shape
    out, lse = _build_stream_fwd_call(B, L, H, D, q.dtype, dtype, rate, blk,
                                      hc, interpret, seg=seg, L_hash=L_hash,
                                      seg_split=seg_split)(
        _row_seeds(seed, B, H),
        base if base is not None else _zero_base(),
        mask[:, None, :], _fold(q), _fold(k), _fold(v)
    )
    return out.reshape(B, L, H, D), _lse_unpack(lse, blk, H)


def _stream_backward(q, k, v, mask, seed, g, out, lse, blk, hc, dtype, rate,
                     interpret, seg=False, base=None, L_hash=None,
                     seg_split=False):
    B, L, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    spec_q = pl.BlockSpec((1, blk, hc * D), lambda b, hj, qi, ki, *_: (b, qi, hj))
    spec_k = pl.BlockSpec((1, blk, hc * D), lambda b, hj, qi, ki, *_: (b, ki, hj))
    spec_lse = pl.BlockSpec((1, 1, 1, hc * blk),
                            lambda b, hj, qi, ki, *_: (b, qi, 0, hj))
    args = (_row_seeds(seed, B, H),
            base if base is not None else _zero_base(),
            mask[:, None, :], _fold(q), _fold(k),
            _fold(v), _fold(g), _fold(out), _lse_pack(lse, blk))

    dq = pl.pallas_call(
        functools.partial(_stream_dq_kernel, scale=scale, rate=rate, hc=hc,
                          D=D, L=L if L_hash is None else L_hash, seg=seg,
                          seg_split=seg_split),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hc, L // blk, L // blk),  # (.., nq, nk): k inner
            in_specs=[
                _stream_mask_spec(L, blk, k_index=3, seg=seg,
                                  seg_split=seg_split),
                spec_q, spec_k, spec_k, spec_q, spec_q, spec_lse,
            ],
            out_specs=[spec_q],
            scratch_shapes=[pltpu.VMEM((blk, hc * D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, L, H * D), q.dtype)],
        interpret=interpret,
        name="flash_bwd",
    )(*args)[0]

    # same residuals, transposed grid: k/v blocks resident, q sweeps
    dkv_args = (args[0], args[1], args[2], args[4], args[5], args[3],
                args[6], args[7], args[8])
    dk, dv = _build_stream_dkv_call(B, L, H, D, q.dtype, rate, blk, hc,
                                    interpret, k_dtype=k.dtype,
                                    v_dtype=v.dtype, seg=seg, L_hash=L_hash,
                                    seg_split=seg_split)(*dkv_args)
    return (dq.reshape(B, L, H, D), dk.reshape(B, L, H, D),
            dv.reshape(B, L, H, D))


def _build_stream_dkv_call(B, L, H, D, in_dtype, rate, blk, hc, interpret,
                           k_dtype=None, v_dtype=None, seg=False,
                           L_hash=None, seg_split=False):
    """The streaming dk/dv ``pallas_call`` for one (blk, hc) — the heaviest
    of the three streaming kernels (two f32 scratch accumulators), so it is
    the one the autotuner probes alongside the forward. ``k_dtype`` /
    ``v_dtype`` default to ``in_dtype`` (the probe's uniform-dtype shape);
    the execution path passes the primals' own dtypes so the cotangents
    match mixed-dtype q/k/v."""
    scale = 1.0 / (D ** 0.5)
    spec_kq = pl.BlockSpec((1, blk, hc * D), lambda b, hj, ki, qi, *_: (b, ki, hj))
    spec_qq = pl.BlockSpec((1, blk, hc * D), lambda b, hj, ki, qi, *_: (b, qi, hj))
    return pl.pallas_call(
        functools.partial(_stream_dkv_kernel, scale=scale, rate=rate, hc=hc,
                          D=D, L=L if L_hash is None else L_hash, seg=seg,
                          seg_split=seg_split),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hc, L // blk, L // blk),  # (.., nk, nq): q inner
            in_specs=[
                _stream_mask_spec(L, blk, k_index=2, seg=seg,
                                  seg_split=seg_split),
                spec_kq, spec_kq, spec_qq, spec_qq, spec_qq,
                pl.BlockSpec((1, 1, 1, hc * blk),
                             lambda b, hj, ki, qi, *_: (b, qi, 0, hj)),
            ],
            out_specs=[spec_kq, spec_kq],
            scratch_shapes=[
                pltpu.VMEM((blk, hc * D), jnp.float32),
                pltpu.VMEM((blk, hc * D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H * D),
                                 k_dtype if k_dtype is not None else in_dtype),
            jax.ShapeDtypeStruct((B, L, H * D),
                                 v_dtype if v_dtype is not None else in_dtype),
        ],
        interpret=interpret,
        name="flash_bwd",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _stream_core(q, k, v, mask, seed, dtype, rate, interpret, seg):
    out, _ = _stream_fwd(q, k, v, mask, seed, dtype, rate, interpret, seg)
    return out


@jax.named_scope("flash_fwd")
def _stream_fwd(q, k, v, mask, seed, dtype, rate, interpret, seg):
    B, L, H, D = q.shape
    cfg = _streaming_geometry(L, H, D, q.dtype, jnp.dtype(dtype), rate,
                              mask_dtype=mask.dtype, interpret=interpret,
                              seg=seg)
    if cfg is None:
        raise ValueError(
            f"no VMEM-feasible streaming config for L={L}, H={H}, D={D} "
            f"(rate={rate}); gate on supports_streaming"
        )
    out, lse = _stream_forward(q, k, v, mask, seed, *cfg, dtype, rate,
                               interpret, seg=seg)
    return out, (q, k, v, mask, seed, out, lse)


@jax.named_scope("flash_bwd")
def _stream_bwd(dtype, rate, interpret, seg, residuals, g):
    q, k, v, mask, seed, out, lse = residuals
    B, L, H, D = q.shape
    # same key as the forward's selection -> the cached geometry, so both
    # directions always run the SAME (blk, hc)
    cfg = _streaming_geometry(L, H, D, q.dtype, jnp.dtype(dtype), rate,
                              mask_dtype=mask.dtype, interpret=interpret,
                              seg=seg)
    dq, dk, dv = _stream_backward(
        q, k, v, mask, seed, g.astype(q.dtype), out, lse, *cfg, dtype, rate,
        interpret, seg=seg,
    )
    return dq, dk, dv, None, None


_stream_core.defvjp(_stream_fwd, _stream_bwd)


def streaming_attention(q, k, v, mask, seed=None, dtype=jnp.float32,
                        rate=0.0, interpret=False, segmented=False):
    """Streaming-KV attention over [B, L, H, D] with a [B, L] key mask —
    the beyond-2k regime (VMEM O(blk^2) per program, any ``L`` a stream
    block divides). Same contract as ``flash_attention``, including the
    ``segmented`` sequence-packing variant (``mask`` then carries segment
    ids; the permission grid is block-diagonal)."""
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), dtype=jnp.int32)
    return _stream_core(q, k, v, mask, seed, dtype, rate, interpret,
                        bool(segmented))

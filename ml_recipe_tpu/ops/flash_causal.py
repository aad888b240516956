"""Causal flash attention over heads whose q/k width differs from v's.

The regimes of ``ops/flash_attention.py`` and ``ops/flash_streaming.py`` take
one ``D`` for q, k and v and mask by key padding or segment id only. A latent
attention block (MLA) in its training form is multi-head attention with
``d_qk = nope + rope`` (192) and ``d_v`` (128) under a causal mask: this module
is that regime. FlashAttention-2 tiling as in the streaming family (online
softmax forward, probabilities recomputed from the saved row logsumexp in the
backward), with three differences:

- operands are ``[B, H, L, D]`` so a block's minor dimension is the whole head
  width, whatever it is (192 is no multiple of the 128-lane tile, so the folded
  ``[B, L, H*D]`` layout's per-head lane slices would not be aligned);
- the grid's last axis walks only the ``n(n+1)/2`` (q block, k block) pairs a
  causal mask leaves non-empty, through two scalar-prefetched tables, so the
  emptied blocks cost neither a DMA nor a grid step. The diagonal blocks
  apply the triangle, the others only the key-padding row;
- the backward is ONE kernel (``flash_causal_bwd``) wherever a (batch, head)
  row's f32 dq fits ``_DQ_ROW_BUDGET`` of VMEM (``fused_backward``): it walks
  the pairs k-outer, the dk/dv order, and adds each tile's ``ds @ k`` into the
  resident row, so QK^T, dP and the probabilities are recomputed once a pair.
  Longer rows take FlashAttention-2's split: a dq kernel (k innermost) and a
  dk/dv kernel (q innermost), each recomputing the tile.

Grouped-query heads (``k`` and ``v`` with ``H_kv = H / group`` heads) enter
through the index maps alone: query head ``h`` reads key/value head
``h // group``, and k and v are never repeated in HBM. The backward writes
dk and dv a QUERY head (the grid's head axis is parallel, so no two heads may
add into one block) and XLA sums each group in f32 after the call: at 32/8
heads of 64 the kernel writes 32 head-rows of each where 8 are needed and the
sum reads them once more, a few tenths of a millisecond beside a backward of
tens. With ``H_kv == H`` every call is the one it was.

No dropout and no segment ids (the published MLA configurations have neither);
``ops/attention.py`` refuses both before it gets here. A query row whose every
permitted key is padding (only possible when key 0 is padding) yields finite
garbage, the other regimes' contract for pad rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_BLOCKS = (512, 256, 128)
_LANES = 128
# VMEM the fused backward may give a row's f32 dq accumulator: L 8,192 at the
# published d_qk 192 (256 lanes); its output block, twice, is as much again
_DQ_ROW_BUDGET = 8 * 2 ** 20
# what a call gets without asking (v5e: 16 MiB of 128): left to the tiles
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def pick_block(L: int):
    """The q/k block edge: the largest of 512/256/128 that divides ``L`` at
    least twice (one block a row leaves nothing to skip), or ``None``."""
    for blk in _BLOCKS:
        if L % blk == 0 and L // blk >= 2:
            return blk
    return None


def supports_causal(L: int, d_qk: int, d_v: int) -> bool:
    """Shapes the kernels take: a block edge divides ``L`` and both head
    widths are multiples of 64 (half a lane tile; the published widths are
    192/128)."""
    return pick_block(L) is not None and d_qk % 64 == 0 and d_v % 64 == 0


def _dq_row_bytes(L: int, d_qk: int, itemsize: int) -> int:
    return L * -(-d_qk // _LANES) * _LANES * itemsize


def fused_backward(L: int, d_qk: int) -> bool:
    """Whether the backward is the one fused kernel: a row's f32 dq
    accumulator ``[L, d_qk]`` (lanes padded) fits ``_DQ_ROW_BUDGET``."""
    return _dq_row_bytes(L, d_qk, 4) <= _DQ_ROW_BUDGET


def _pairs(n: int, *, k_outer: bool) -> np.ndarray:
    """``[2, n(n+1)/2]`` int32: the (q block, k block) pairs with ``k <= q``,
    k innermost (forward, dq) or q innermost (dk/dv, the fused backward)."""
    if k_outer:
        pairs = [(qi, ki) for ki in range(n) for qi in range(ki, n)]
    else:
        pairs = [(qi, ki) for qi in range(n) for ki in range(qi + 1)]
    return np.asarray(pairs, np.int32).T


def _scores(q, k, mask_row, scale, diagonal: bool):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    allowed = mask_row[None, :] > 0
    if diagonal:
        blk = s.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        allowed = allowed & (cols <= rows)
    return jnp.where(allowed, s, _NEG_INF)


def _fwd_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]

    def step(diagonal: bool):
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], mask_ref[0, 0, :], scale,
                    diagonal)
        first = ki == 0
        m_old = jnp.where(first, jnp.float32(_NEG_INF), m_ref[...])
        l_old = jnp.where(first, 0.0, l_ref[...])
        acc_old = jnp.where(first, 0.0, acc_ref[...])
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        e = jnp.exp(s - m_new)
        l_new = alpha * l_old + jnp.sum(e, axis=-1, keepdims=True)
        acc_new = alpha * acc_old + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new
        if diagonal:        # the row's last permitted block
            o_ref[0, 0] = (acc_new * (1.0 / l_new)).astype(o_ref.dtype)
            lse_ref[0, 0, 0, :] = (m_new + jnp.log(l_new))[:, 0]

    pl.when(ki == qi)(lambda: step(True))
    pl.when(ki < qi)(lambda: step(False))


def _tile_grads(q, k, v, g, lse, delta, mask_row, scale, diagonal: bool):
    """``(p, ds)`` of one tile in f32: probabilities from the saved row
    logsumexp, the softmax row term from ``delta = g . out``."""
    s = _scores(q, k, mask_row, scale, diagonal)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta)


def _dq_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, scale: float):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]

    def step(diagonal: bool):
        k = k_ref[0, 0]
        _, ds = _tile_grads(
            q_ref[0, 0], k, v_ref[0, 0], g_ref[0, 0],
            lse_ref[0, 0, 0, :][:, None], delta_ref[0, 0, 0, :][:, None],
            mask_ref[0, 0, :], scale, diagonal)
        acc = jnp.where(ki == 0, 0.0, acc_ref[...]) + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc
        if diagonal:
            dq_ref[0, 0] = (acc * scale).astype(dq_ref.dtype)

    pl.when(ki == qi)(lambda: step(True))
    pl.when(ki < qi)(lambda: step(False))


def _kv_major_kernel(qi_ref, ki_ref, mask_ref, k_ref, v_ref, q_ref, g_ref,
                     lse_ref, delta_ref, *outs_and_scratch, scale: float,
                     n_blocks: int, fused: bool):
    """The backward over the k-outer pairs. dk/dv accumulate over a k block's
    column, from its diagonal pair (which starts them) to the last q block
    (which stores them). ``fused`` (outputs ``dq, dk, dv`` and an accumulator
    each) makes dq too, from the same recomputation of the pair: a q block's
    dq gathers in its rows of the whole-row f32 scratch from k block 0 (first
    written) to its diagonal (its last: scaled, cast and stored into the
    resident output row, which leaves VMEM when the row is done). Otherwise
    (``dk, dv`` and two accumulators) it is the split backward's dk/dv half."""
    if fused:
        dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = \
            outs_and_scratch
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = outs_and_scratch
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    blk = q_ref.shape[2]

    def step(diagonal: bool):
        q, k, g = q_ref[0, 0], k_ref[0, 0], g_ref[0, 0]
        p, ds = _tile_grads(
            q, k, v_ref[0, 0], g,
            lse_ref[0, 0, 0, :][:, None], delta_ref[0, 0, 0, :][:, None],
            mask_ref[0, 0, :], scale, diagonal)
        if fused:
            rows = pl.ds(pl.multiple_of(qi * blk, blk), blk)
            dq_acc = jnp.where(ki == 0, 0.0, dq_acc_ref[rows, :]) \
                + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if diagonal:
                dq_ref[0, 0, rows, :] = (dq_acc * scale).astype(dq_ref.dtype)
            else:
                dq_acc_ref[rows, :] = dq_acc
        dv_acc = jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if not diagonal:
            dv_acc += dv_acc_ref[...]
            dk_acc += dk_acc_ref[...]
        dv_acc_ref[...] = dv_acc
        dk_acc_ref[...] = dk_acc

        @pl.when(qi == n_blocks - 1)
        def _finish():
            dk_ref[0, 0] = (dk_acc * scale).astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)

    pl.when(ki == qi)(lambda: step(True))
    pl.when(ki < qi)(lambda: step(False))


def _specs(blk, d_qk, d_v, group=1):
    """Block specs over the (batch, head, pair) grid; the pair's q and k
    block come from the prefetched tables. ``k`` and ``v`` are the inputs'
    ``[B, H / group, L, width]``; ``dk`` and ``dv`` are a query head's."""
    def rows(width, table, group=1):     # a [B, H / group, L, width] operand
        head = (lambda h: h) if group == 1 else (lambda h: h // group)
        return pl.BlockSpec(
            (1, 1, blk, width),
            lambda b, h, t, qi, ki: (b, head(h), (qi, ki)[table][t], 0))

    def row_stat():                  # a [B, H, 1, L] f32 row statistic, by q
        return pl.BlockSpec(
            (1, 1, 1, blk), lambda b, h, t, qi, ki: (b, h, 0, qi[t]))

    mask = pl.BlockSpec((1, 1, blk), lambda b, h, t, qi, ki: (b, 0, ki[t]))
    return {"q": rows(d_qk, 0), "k": rows(d_qk, 1, group),
            "v": rows(d_v, 1, group), "dk": rows(d_qk, 1), "dv": rows(d_v, 1),
            "o": rows(d_v, 0), "stat": row_stat(), "mask": mask}


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, vmem_limit_bytes=None):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
    )


def build_fwd_call(B, H, L, d_qk, d_v, in_dtype, out_dtype, interpret=False,
                   group=1):
    """The forward ``pallas_call`` (shared with the chip-compile test);
    ``group`` query heads read one key/value head."""
    blk = pick_block(L)
    n = L // blk
    sp = _specs(blk, d_qk, d_v, group)
    return _call(
        functools.partial(_fwd_kernel, scale=1.0 / (d_qk ** 0.5)),
        "flash_causal_fwd", (B, H, n * (n + 1) // 2),
        [sp["mask"], sp["q"], sp["k"], sp["v"]], [sp["o"], sp["stat"]],
        [jax.ShapeDtypeStruct((B, H, L, d_v), out_dtype),
         jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32)],
        [pltpu.VMEM((blk, d_v), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32)],
        interpret,
    )


def build_bwd_calls(B, H, L, d_qk, d_v, in_dtype, interpret=False, group=1):
    """The backward's ``pallas_call``s: ``(fused,)`` where ``fused_backward``
    says the row's dq fits VMEM, else ``(dq, dk/dv)``. The fused call and the
    dk/dv call take ``tables(k_outer=True), mask, k, v, q, g, lse, delta``; the
    dq call ``tables(k_outer=False), mask, q, k, v, g, lse, delta``. dk and dv
    come out a query head, ``[B, H, L, d]``, whatever ``group`` is."""
    blk = pick_block(L)
    n = L // blk
    sp = _specs(blk, d_qk, d_v, group)
    scale = 1.0 / (d_qk ** 0.5)
    grid = (B, H, n * (n + 1) // 2)
    kv_major = [sp["mask"], sp["k"], sp["v"], sp["q"], sp["o"], sp["stat"],
                sp["stat"]]
    wide, narrow = (jax.ShapeDtypeStruct((B, H, L, d), in_dtype)
                    for d in (d_qk, d_v))
    kv_scratch = [pltpu.VMEM((blk, d_qk), jnp.float32),
                  pltpu.VMEM((blk, d_v), jnp.float32)]
    if fused_backward(L, d_qk):
        # dq's block is the (batch, head) row: constant over the pair axis,
        # so it stays in VMEM and is written back once. The accumulator and
        # that block's two buffers come on top of what the tiles take.
        dq_row = pl.BlockSpec((1, 1, L, d_qk),
                              lambda b, h, t, qi, ki: (b, h, 0, 0))
        resident = _dq_row_bytes(L, d_qk, 4) + 2 * _dq_row_bytes(
            L, d_qk, jnp.dtype(in_dtype).itemsize)
        return (_call(
            functools.partial(_kv_major_kernel, scale=scale, n_blocks=n,
                              fused=True),
            "flash_causal_bwd", grid, kv_major, [dq_row, sp["dk"], sp["dv"]],
            [wide, wide, narrow],
            [pltpu.VMEM((L, d_qk), jnp.float32)] + kv_scratch, interpret,
            vmem_limit_bytes=_DEFAULT_SCOPED_VMEM + resident),)
    dq = _call(
        functools.partial(_dq_kernel, scale=scale), "flash_causal_bwd_dq",
        grid,
        [sp["mask"], sp["q"], sp["k"], sp["v"], sp["o"], sp["stat"],
         sp["stat"]],
        [sp["q"]], [wide], [pltpu.VMEM((blk, d_qk), jnp.float32)], interpret,
    )
    dkv = _call(
        functools.partial(_kv_major_kernel, scale=scale, n_blocks=n,
                          fused=False),
        "flash_causal_bwd_dkv", grid, kv_major, [sp["dk"], sp["dv"]],
        [wide, narrow], kv_scratch, interpret,
    )
    return dq, dkv


def _tables(L, *, k_outer: bool):
    qi, ki = _pairs(L // pick_block(L), k_outer=k_outer)
    return jnp.asarray(qi), jnp.asarray(ki)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _core(q, k, v, mask, dtype, interpret):
    return _core_fwd(q, k, v, mask, dtype, interpret)[0]


@jax.named_scope("flash_fwd")
def _core_fwd(q, k, v, mask, dtype, interpret):
    B, H, L, d_qk = q.shape
    out, lse = build_fwd_call(B, H, L, d_qk, v.shape[-1], q.dtype, dtype,
                              interpret, H // k.shape[1])(
        *_tables(L, k_outer=False), mask[:, None, :], q, k, v)
    return out, (q, k, v, mask, out, lse)


@jax.named_scope("flash_bwd")
def _core_bwd(dtype, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    B, H, L, d_qk = q.shape
    g = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    group = H // k.shape[1]
    calls = build_bwd_calls(B, H, L, d_qk, v.shape[-1], q.dtype, interpret,
                            group)
    kv_major = (*_tables(L, k_outer=True), mask[:, None, :], k, v, q, g, lse,
                delta)
    if len(calls) == 1:
        dq, dk, dv = calls[0](*kv_major)
    else:
        dq = calls[0](*_tables(L, k_outer=False), mask[:, None, :], q, k, v,
                      g, lse, delta)[0]
        dk, dv = calls[1](*kv_major)
    if group > 1:       # a key/value head's gradient: the sum over its group
        dk, dv = (jnp.sum(d.reshape(B, H // group, group, L, d.shape[-1]),
                          axis=2, dtype=jnp.float32).astype(d.dtype)
                  for d in (dk, dv))
    return dq, dk, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def causal_attention(q, k, v, mask=None, *, dtype=jnp.float32,
                     interpret: bool = False):
    """``softmax(q k^T / sqrt(d_qk) + causal + key-pad) v`` over
    ``[B, L, H, d_qk]`` q, ``[B, L, H_kv, d_qk]`` k and ``[B, L, H_kv, d_v]``
    v (query head ``h`` reads key/value head ``h // (H / H_kv)``) with a
    ``[B, L]`` key mask (1 = real); returns ``[B, L, H, d_v]`` in ``dtype``."""
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    out = _core(heads_first(q), heads_first(k), heads_first(v),
                mask.astype(jnp.int32), jnp.dtype(dtype), interpret)
    return jnp.transpose(out, (0, 2, 1, 3))

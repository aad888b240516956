"""Causal flash attention under a sliding window: query ``i`` reads the keys
``j`` with ``0 <= i - j < window`` (itself and the ``window - 1`` before it).

The causal family (``ops/flash_causal.py``) walks the ``n(n+1)/2`` (q block,
k block) pairs a causal mask leaves; a window cuts the tables BELOW the
diagonal too. With ``reach = ceil((window - 1) / blk)`` a q block ``a`` keeps
the k blocks ``max(0, a - reach) <= b <= a``, whatever ``L`` is, so a window
layer's grid grows with ``L`` and not ``L^2``. A pair's distance ``d = a - b``
says which mask its tile needs: the diagonal (``d == 0``) the triangle
``j <= i``; a far tile (``d >= edge``, the first distance whose tile holds an
``i - j >= window``) the band's other side ``i - j < window``; the tiles
between neither. At a window of 1,024 and a block edge of 512 a q block
walks three tiles: the diagonal's lower triangle, one whole block and the far
edge's strict upper triangle (1,536 keys computed for 1,024 needed; an edge
of 256 would walk five tiles for 1,280 keys, each a quarter of the work of a
512 tile under the same grid-step cost, so the edge stays ``pick_block``'s,
from the shapes alone).

These are bodies of their own, in a file of their own: a Mosaic body carries
its file path and line numbers into the compile cache's key, so nothing of
``ops/flash_causal.py`` moves and ``window=None`` traces what it traced.
Shared with it: the block specs, the call builder, the block edge, the fused
backward's VMEM rule, the layout ``[B, H, L, D]`` and grouped-query heads
through the index maps (the backward writes dk and dv a QUERY head and XLA
sums each group in f32 after the call). The calls carry names of their own
(``flash_window_fwd``, ``flash_window_bwd``, ``_bwd_dq``, ``_bwd_dkv``): the
trace readers tell window calls from full ones by them.

A far tile's upper rows see none of its keys; their running maximum stays at
the mask's value until a nearer tile brings a permitted key (the diagonal
always does: key ``i`` for query ``i``), whose rescaling then wipes what the
masked tile summed. No dropout and no segment ids, as in the causal family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_causal import (_DEFAULT_SCOPED_VMEM, _NEG_INF, _call,
                           _dq_row_bytes, _specs, fused_backward, pick_block,
                           supports_causal)


def reach(window: int, blk: int) -> int:
    """How many k blocks behind its own a q block reads."""
    return -(-(window - 1) // blk)


def edge(window: int, blk: int) -> int:
    """The least block distance whose tile holds a pair with ``i - j >=
    window`` (its largest is ``d * blk + blk - 1``): tiles from there on mask
    the band's far side."""
    return max(0, -(-(window - blk + 1) // blk))


def supports_window(L: int, d_qk: int, d_v: int, window: int) -> bool:
    """Shapes the kernels take: the causal family's, and a window that cuts
    something (``1 <= window < L``; at ``window >= L`` it is the causal
    mask)."""
    return supports_causal(L, d_qk, d_v) and 1 <= window < L


def pairs(n: int, behind: int, *, k_outer: bool) -> np.ndarray:
    """``[2, pairs]`` int32: the (q block, k block) pairs with ``q - behind
    <= k <= q``, k innermost (forward, dq) or q innermost (dk/dv, the fused
    backward)."""
    if k_outer:
        walk = [(qi, ki) for ki in range(n)
                for qi in range(ki, min(n, ki + behind + 1))]
    else:
        walk = [(qi, ki) for qi in range(n)
                for ki in range(max(0, qi - behind), qi + 1)]
    return np.asarray(walk, np.int32).T


def block_pairs(L: int, window: int) -> tuple:
    """``(walked, causal)``: the pairs one call's grid walks a (row, head)
    under the window, and those the causal triangle holds at the same block
    edge; ``(0, 0)`` for a length no block edge divides."""
    blk = pick_block(L)
    if blk is None:
        return 0, 0
    n = L // blk
    return pairs(n, reach(window, blk), k_outer=False).shape[1], \
        n * (n + 1) // 2


def _scores(q, k, mask_row, scale, distance, *, diagonal: bool, far: bool,
            window: int):
    """A tile's masked logits in f32. ``distance``: the pair's q block less
    its k block (a traced scalar; only a ``far`` tile reads it)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    allowed = mask_row[None, :] > 0
    if diagonal or far:
        blk = s.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        if diagonal:
            allowed = allowed & (cols <= rows)
        if far:
            allowed = allowed & (rows - cols < window - distance * blk)
    return jnp.where(allowed, s, _NEG_INF)


def _by_distance(qi, ki, step, *, window: int, blk: int):
    """Run ``step(diagonal, far)`` for the pair's kind of tile."""
    first_far = edge(window, blk)
    distance = qi - ki
    pl.when(distance == 0)(lambda: step(True, first_far == 0))
    if first_far > 1:
        pl.when((distance > 0) & (distance < first_far))(
            lambda: step(False, False))
    pl.when(distance >= max(first_far, 1))(lambda: step(False, True))


def _fwd_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, window: int,
                behind: int):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    blk = q_ref.shape[2]

    def step(diagonal: bool, far: bool):
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], mask_ref[0, 0, :], scale,
                    qi - ki, diagonal=diagonal, far=far, window=window)
        first = ki == jnp.maximum(qi - behind, 0)
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

    _by_distance(qi, ki, step, window=window, blk=blk)


def _tile_grads(q, k, v, g, lse, delta, mask_row, scale, distance, *,
                diagonal: bool, far: bool, window: int):
    """``(p, ds)`` of one tile in f32: probabilities from the saved row
    logsumexp, the softmax row term from ``delta = g . out``."""
    s = _scores(q, k, mask_row, scale, distance, diagonal=diagonal, far=far,
                window=window)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta)


def _dq_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, scale: float, window: int,
               behind: int):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    blk = q_ref.shape[2]

    def step(diagonal: bool, far: bool):
        k = k_ref[0, 0]
        _, ds = _tile_grads(
            q_ref[0, 0], k, v_ref[0, 0], g_ref[0, 0],
            lse_ref[0, 0, 0, :][:, None], delta_ref[0, 0, 0, :][:, None],
            mask_ref[0, 0, :], scale, qi - ki, diagonal=diagonal, far=far,
            window=window)
        first = ki == jnp.maximum(qi - behind, 0)
        acc = jnp.where(first, 0.0, acc_ref[...]) + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc
        if diagonal:
            dq_ref[0, 0] = (acc * scale).astype(dq_ref.dtype)

    _by_distance(qi, ki, step, window=window, blk=blk)


def _kv_major_kernel(qi_ref, ki_ref, mask_ref, k_ref, v_ref, q_ref, g_ref,
                     lse_ref, delta_ref, *outs_and_scratch, scale: float,
                     n_blocks: int, fused: bool, window: int, behind: int):
    """The backward over the k-outer pairs. dk/dv accumulate over a k block's
    column, from its diagonal pair (which starts them) to the last q block
    that reads it (``min(k + behind, n - 1)``, which stores them). ``fused``
    makes dq too, from the same recomputation of the pair: a q block's dq
    gathers in its rows of the whole-row f32 scratch from the farthest k block
    it reads (first written) to its diagonal (its last: scaled, cast and
    stored into the resident output row). Otherwise it is the split
    backward's dk/dv half."""
    if fused:
        dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = \
            outs_and_scratch
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = outs_and_scratch
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    blk = q_ref.shape[2]

    def step(diagonal: bool, far: bool):
        q, k, g = q_ref[0, 0], k_ref[0, 0], g_ref[0, 0]
        p, ds = _tile_grads(
            q, k, v_ref[0, 0], g,
            lse_ref[0, 0, 0, :][:, None], delta_ref[0, 0, 0, :][:, None],
            mask_ref[0, 0, :], scale, qi - ki, diagonal=diagonal, far=far,
            window=window)
        if fused:
            rows = pl.ds(pl.multiple_of(qi * blk, blk), blk)
            first = ki == jnp.maximum(qi - behind, 0)
            dq_acc = jnp.where(first, 0.0, dq_acc_ref[rows, :]) \
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

        @pl.when(qi == jnp.minimum(ki + behind, n_blocks - 1))
        def _finish():
            dk_ref[0, 0] = (dk_acc * scale).astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)

    _by_distance(qi, ki, step, window=window, blk=blk)


def _geometry(L, window, blk):
    blk = blk or pick_block(L)
    n, behind = L // blk, reach(window, blk)
    return blk, n, behind, pairs(n, behind, k_outer=False).shape[1]


def build_fwd_call(B, H, L, d_qk, d_v, window, in_dtype, out_dtype,
                   interpret=False, group=1, blk=None):
    """The forward ``pallas_call`` (shared with the chip-compile test);
    ``group`` query heads read one key/value head. ``blk``: another block
    edge than ``pick_block``'s, for a measurement outside the program."""
    blk, n, behind, walked = _geometry(L, window, blk)
    sp = _specs(blk, d_qk, d_v, group)
    return _call(
        functools.partial(_fwd_kernel, scale=1.0 / (d_qk ** 0.5),
                          window=window, behind=behind),
        "flash_window_fwd", (B, H, walked),
        [sp["mask"], sp["q"], sp["k"], sp["v"]], [sp["o"], sp["stat"]],
        [jax.ShapeDtypeStruct((B, H, L, d_v), out_dtype),
         jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32)],
        [pltpu.VMEM((blk, d_v), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32)],
        interpret,
    )


def build_bwd_calls(B, H, L, d_qk, d_v, window, in_dtype, interpret=False,
                    group=1, blk=None):
    """The backward's ``pallas_call``s, as ``flash_causal.build_bwd_calls``:
    ``(fused,)`` where a row's f32 dq fits VMEM, else ``(dq, dk/dv)``; the
    same operands in the same order."""
    blk, n, behind, walked = _geometry(L, window, blk)
    sp = _specs(blk, d_qk, d_v, group)
    how = dict(scale=1.0 / (d_qk ** 0.5), window=window, behind=behind)
    grid = (B, H, walked)
    kv_major = [sp["mask"], sp["k"], sp["v"], sp["q"], sp["o"], sp["stat"],
                sp["stat"]]
    wide, narrow = (jax.ShapeDtypeStruct((B, H, L, d), in_dtype)
                    for d in (d_qk, d_v))
    kv_scratch = [pltpu.VMEM((blk, d_qk), jnp.float32),
                  pltpu.VMEM((blk, d_v), jnp.float32)]
    if fused_backward(L, d_qk):
        dq_row = pl.BlockSpec((1, 1, L, d_qk),
                              lambda b, h, t, qi, ki: (b, h, 0, 0))
        resident = _dq_row_bytes(L, d_qk, 4) + 2 * _dq_row_bytes(
            L, d_qk, jnp.dtype(in_dtype).itemsize)
        return (_call(
            functools.partial(_kv_major_kernel, n_blocks=n, fused=True,
                              **how),
            "flash_window_bwd", grid, kv_major, [dq_row, sp["dk"], sp["dv"]],
            [wide, wide, narrow],
            [pltpu.VMEM((L, d_qk), jnp.float32)] + kv_scratch, interpret,
            vmem_limit_bytes=_DEFAULT_SCOPED_VMEM + resident),)
    dq = _call(
        functools.partial(_dq_kernel, **how), "flash_window_bwd_dq", grid,
        [sp["mask"], sp["q"], sp["k"], sp["v"], sp["o"], sp["stat"],
         sp["stat"]],
        [sp["q"]], [wide], [pltpu.VMEM((blk, d_qk), jnp.float32)], interpret,
    )
    dkv = _call(
        functools.partial(_kv_major_kernel, n_blocks=n, fused=False, **how),
        "flash_window_bwd_dkv", grid, kv_major, [sp["dk"], sp["dv"]],
        [wide, narrow], kv_scratch, interpret,
    )
    return dq, dkv


def _tables(L, window, *, k_outer: bool):
    blk = pick_block(L)
    qi, ki = pairs(L // blk, reach(window, blk), k_outer=k_outer)
    return jnp.asarray(qi), jnp.asarray(ki)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _core(q, k, v, mask, window, dtype, interpret):
    return _core_fwd(q, k, v, mask, window, dtype, interpret)[0]


@jax.named_scope("flash_fwd")
def _core_fwd(q, k, v, mask, window, dtype, interpret):
    B, H, L, d_qk = q.shape
    out, lse = build_fwd_call(B, H, L, d_qk, v.shape[-1], window, q.dtype,
                              dtype, interpret, H // k.shape[1])(
        *_tables(L, window, k_outer=False), mask[:, None, :], q, k, v)
    return out, (q, k, v, mask, out, lse)


@jax.named_scope("flash_bwd")
def _core_bwd(window, dtype, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    B, H, L, d_qk = q.shape
    g = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    group = H // k.shape[1]
    calls = build_bwd_calls(B, H, L, d_qk, v.shape[-1], window, q.dtype,
                            interpret, group)
    kv_major = (*_tables(L, window, k_outer=True), mask[:, None, :], k, v, q,
                g, lse, delta)
    if len(calls) == 1:
        dq, dk, dv = calls[0](*kv_major)
    else:
        dq = calls[0](*_tables(L, window, k_outer=False), mask[:, None, :], q,
                      k, v, g, lse, delta)[0]
        dk, dv = calls[1](*kv_major)
    if group > 1:       # a key/value head's gradient: the sum over its group
        dk, dv = (jnp.sum(d.reshape(B, H // group, group, L, d.shape[-1]),
                          axis=2, dtype=jnp.float32).astype(d.dtype)
                  for d in (dk, dv))
    return dq, dk, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def window_attention(q, k, v, mask=None, *, window: int, dtype=jnp.float32,
                     interpret: bool = False):
    """``softmax(q k^T / sqrt(d_qk) + band + key-pad) v`` over ``[B, L, H,
    d_qk]`` q, ``[B, L, H_kv, d_qk]`` k and ``[B, L, H_kv, d_v]`` v (query
    head ``h`` reads key/value head ``h // (H / H_kv)``), the band ``0 <= i -
    j < window``, with a ``[B, L]`` key mask (1 = real); returns ``[B, L, H,
    d_v]`` in ``dtype``."""
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    out = _core(heads_first(q), heads_first(k), heads_first(v),
                mask.astype(jnp.int32), int(window), jnp.dtype(dtype),
                interpret)
    return jnp.transpose(out, (0, 2, 1, 3))

"""Causal flash attention over heads whose q/k width differs from v's, and
the kernel bodies the sliding-window family (``ops/flash_window.py``) runs
under its own tables and call names.

The regimes of ``ops/flash_attention.py`` and ``ops/flash_streaming.py`` take
one ``D`` for q, k and v and mask by key padding or segment id only. A latent
attention block (MLA) in its training form is multi-head attention with
``d_qk = nope + rope`` (192) and ``d_v`` (128) under a causal mask: this module
is that regime. FlashAttention-2 tiling as in the streaming family (online
softmax forward, probabilities recomputed from the saved row logsumexp in the
backward), with these differences:

- operands are ``[B, H, L, D]`` so a block's minor dimension is the whole head
  width, whatever it is (192 is no multiple of the 128-lane tile, so the folded
  ``[B, L, H*D]`` layout's per-head lane slices would not be aligned);
- the grid's last axis walks only the ``n(n+1)/2`` (q block, k block) pairs a
  causal mask leaves non-empty, through two scalar-prefetched tables, so the
  emptied blocks cost neither a DMA nor a grid step;
- a tile that a mask edge cuts computes only the row strips and column ranges
  the mask can reach (``strip_cuts``): the diagonal tile's 512 q rows go as
  static strips of ``s`` rows, strip ``r`` reading the k columns ``[0, (r +
  1) s)``. At ``s`` 128 that is 10 of the tile's 16 sub-tiles of 128 x 128
  (12 at 256); the masks inside a strip stay, and every skipped column is one
  whose probability is exactly 0. ``s`` comes from the shapes alone
  (``strip_height``); ``s = blk`` is the whole tile. The other tiles apply
  the key-padding row only and compute whole;
- the backward is ONE kernel (``flash_causal_bwd``) wherever a (batch, head)
  row's f32 dq fits ``_DQ_ROW_BUDGET`` of VMEM (``fused_backward``): it walks
  the pairs k-outer, the dk/dv order, and adds each strip's ``ds @ k`` into
  its rows of the resident row, so QK^T, dP and the probabilities are
  recomputed once a pair. Longer rows take FlashAttention-2's split: a dq
  kernel (k innermost) and a dk/dv kernel (q innermost), each recomputing the
  tile under the same cuts.

What a family's grid walks is a ``Walk``: the block edge, how many k blocks
a q block reads behind its own (the causal triangle: all of them), the kinds
of tile by block distance with their cuts and masks, and the calls' names.
The bodies below read nothing else, so the window family is a ``Walk`` of its
own (``flash_window.window_walk``) over these bodies.

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_forms

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


def strip_height(blk: int) -> int:
    """The row strips' height ``s`` of a tile a mask edge cuts: 128, a
    strip's logits one lane tile deep (the whole tile at a block edge of
    128). Per call on a v5e, forward + backward at 128 / 256 / whole tiles:
    the window family at 32/4 heads of 128 and L 8,192 7.66 / 8.02 / 8.18
    ms; the causal family at 32 heads of 192/128 and L 4,096 16.07 / 16.21 /
    16.56, at 30 of 128 and L 8,192 18.71 / 18.81 / 19.00, at 32/8 of 64
    38.54 / 38.52 / 38.70: 128 is the best or level at every width, so the
    rule reads the block edge alone."""
    return min(blk, _LANES)


def strip_cuts(blk: int, s: int, distance: int,
               window: Optional[int] = None) -> tuple:
    """``(first row, first column, end column)`` of each ``s``-row strip of a
    tile ``distance`` blocks below the diagonal under the band ``0 <= i - j
    < window`` (``None``: the causal triangle): the columns the strip's
    permitted pairs can reach, in 128-lane steps and at least one. ``s ==
    blk``: the whole tile."""
    if s == blk:
        return ((0, 0, blk),)
    cuts = []
    for row in range(0, blk, s):
        end = row + s if distance == 0 else blk     # j <= i
        first = 0 if window is None else max(0, row + distance * blk
                                             - window + 1)   # i - j < window
        cuts.append((row, min(first // _LANES * _LANES, end - _LANES), end))
    return tuple(cuts)


def pairs(n: int, behind: int, *, k_outer: bool) -> np.ndarray:
    """``[2, pairs]`` int32: the (q block, k block) pairs with ``q - behind
    <= k <= q`` (``behind = n - 1``: the causal triangle), k innermost
    (forward, dq) or q innermost (dk/dv, the fused backward)."""
    if k_outer:
        walk = [(qi, ki) for ki in range(n)
                for qi in range(ki, min(n, ki + behind + 1))]
    else:
        walk = [(qi, ki) for qi in range(n)
                for ki in range(max(0, qi - behind), qi + 1)]
    return np.asarray(walk, np.int32).T


class Tile(NamedTuple):
    """A kind of (q block, k block) pair by its block distance ``d = q - k``:
    ``near <= d <= far`` (``far`` None: no bound). It computes the strips
    ``cuts`` (``strip_cuts``); ``diagonal`` keeps ``j <= i`` and ``band``
    keeps ``i - j < band``, in the tile's own coordinates."""
    near: int
    far: Optional[int]
    cuts: tuple
    diagonal: bool
    band: Optional[int]


class Walk(NamedTuple):
    """What one family's grid walks a (batch, head) row, all static: the
    calls' name prefix, the block edge, the blocks a row, the k blocks a q
    block reads behind its own, and the kinds of tile."""
    name: str
    blk: int
    n: int
    behind: int
    tiles: tuple

    def pairs(self, *, k_outer: bool) -> np.ndarray:
        return pairs(self.n, self.behind, k_outer=k_outer)

    def tables(self, *, k_outer: bool):
        qi, ki = self.pairs(k_outer=k_outer)
        return jnp.asarray(qi), jnp.asarray(ki)

    def tile(self, distance: int) -> Tile:
        return next(t for t in self.tiles if t.near <= distance
                    and (t.far is None or distance <= t.far))

    def area(self) -> tuple:
        """``(computed, walked)``: the 128 x 128 sub-tiles one call's grid
        computes a (row, head) under its cuts, and those its pairs hold."""
        qi, ki = self.pairs(k_outer=False)
        computed = sum(_strip(cuts, self.blk) * (end - first)
                       for cuts in (self.tile(d).cuts for d in qi - ki)
                       for _, first, end in cuts)
        return (computed // _LANES ** 2, len(qi) * (self.blk // _LANES) ** 2)


def _strip(cuts, blk: int) -> int:
    """The strips' height: they split the tile's rows evenly."""
    return blk // len(cuts)


def causal_walk(L: int, blk: int, s: int) -> Walk:
    """The causal triangle at block edge ``blk``: the diagonal tile in strips
    of ``s`` rows, the tiles below it whole."""
    n = L // blk
    return Walk("flash_causal", blk, n, n - 1, (
        Tile(0, 0, strip_cuts(blk, s, 0), True, None),
        Tile(1, None, ((0, 0, blk),), False, None)))


def _scores(q, k, mask_row, scale, offset: int, tile: Tile):
    """A strip's masked logits in f32; ``offset``: its first row less its
    first column, in the tile's coordinates."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    allowed = mask_row[None, :] > 0
    if tile.diagonal or tile.band is not None:
        below = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                 - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + offset)
        if tile.diagonal:
            allowed = allowed & (below >= 0)
        if tile.band is not None:
            allowed = allowed & (below < tile.band)
    return jnp.where(allowed, s, _NEG_INF)


def _by_tile(qi, ki, step, walk: Walk):
    """Run ``step(tile)`` for the pair's kind of tile."""
    distance = qi - ki
    for tile in walk.tiles:
        if tile.far == tile.near:
            when = distance == tile.near
        else:
            when = distance >= tile.near
            if tile.far is not None:
                when = when & (distance <= tile.far)
        pl.when(when)(functools.partial(step, tile))


def _strip_operands(cut, s, q_ref, k_ref, v_ref, mask_ref):
    row, first, end = cut
    rows, cols = pl.ds(row, s), pl.ds(first, end - first)
    return (rows, cols, q_ref[0, 0, rows, :], k_ref[0, 0, cols, :],
            v_ref[0, 0, cols, :], mask_ref[0, 0, cols])


def _fwd_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, walk: Walk):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    first = ki == jnp.maximum(qi - walk.behind, 0)

    def step(tile: Tile):
        s = _strip(tile.cuts, walk.blk)
        for cut in tile.cuts:
            rows, _, q, k, v, mask_row = _strip_operands(
                cut, s, q_ref, k_ref, v_ref, mask_ref)
            x = _scores(q, k, mask_row, scale, cut[0] - cut[1], tile)
            m_old = jnp.where(first, jnp.float32(_NEG_INF), m_ref[rows, :])
            l_old = jnp.where(first, 0.0, l_ref[rows, :])
            acc_old = jnp.where(first, 0.0, acc_ref[rows, :])
            m_new = jnp.maximum(m_old, jnp.max(x, axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            e = jnp.exp(x - m_new)
            l_new = alpha * l_old + jnp.sum(e, axis=-1, keepdims=True)
            acc_new = alpha * acc_old + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_new
            acc_ref[rows, :] = acc_new
            if tile.diagonal:       # the row's last permitted block
                o_ref[0, 0, rows, :] = (acc_new * (1.0 / l_new)).astype(
                    o_ref.dtype)
                lse_ref[0, 0, 0, rows] = (m_new + jnp.log(l_new))[:, 0]

    _by_tile(qi, ki, step, walk)


def _strip_grads(cut, s, tile, scale, q_ref, k_ref, v_ref, g_ref, lse_ref,
                 delta_ref, mask_ref):
    """``(rows, cols, q, k, g, p, ds)`` of one strip in f32: probabilities
    from the saved row logsumexp, the softmax row term from ``delta = g .
    out``."""
    rows, cols, q, k, v, mask_row = _strip_operands(
        cut, s, q_ref, k_ref, v_ref, mask_ref)
    g = g_ref[0, 0, rows, :]
    x = _scores(q, k, mask_row, scale, cut[0] - cut[1], tile)
    p = jnp.exp(x - lse_ref[0, 0, 0, rows][:, None])
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return rows, cols, q, k, g, p, p * (dp - delta_ref[0, 0, 0, rows][:, None])


def _dq_kernel(qi_ref, ki_ref, mask_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, scale: float, walk: Walk):
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    first = ki == jnp.maximum(qi - walk.behind, 0)

    def step(tile: Tile):
        s = _strip(tile.cuts, walk.blk)
        for cut in tile.cuts:
            rows, _, _, k, _, _, ds = _strip_grads(
                cut, s, tile, scale, q_ref, k_ref, v_ref, g_ref, lse_ref,
                delta_ref, mask_ref)
            acc = jnp.where(first, 0.0, acc_ref[rows, :]) \
                + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            acc_ref[rows, :] = acc
            if tile.diagonal:
                dq_ref[0, 0, rows, :] = (acc * scale).astype(dq_ref.dtype)

    _by_tile(qi, ki, step, walk)


def _kv_major_kernel(qi_ref, ki_ref, mask_ref, k_ref, v_ref, q_ref, g_ref,
                     lse_ref, delta_ref, *outs_and_scratch, scale: float,
                     walk: Walk, fused: bool):
    """The backward over the k-outer pairs. dk/dv accumulate over a k block's
    column, from its diagonal pair (which opens them) to the last q block
    that reads it (``min(k + behind, n - 1)``, which stores them); a strip
    adds into the columns it read. ``fused`` (outputs ``dq, dk, dv`` and an
    accumulator each) makes dq too, from the same recomputation of the strip:
    a q block's dq gathers in its rows of the whole-row f32 scratch from the
    farthest k block it reads (first written) to its diagonal (its last:
    scaled, cast and stored into the resident output row, which leaves VMEM
    when the row is done). Otherwise (``dk, dv`` and two accumulators) it is
    the split backward's dk/dv half."""
    if fused:
        dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = \
            outs_and_scratch
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = outs_and_scratch
    t = pl.program_id(2)
    qi, ki = qi_ref[t], ki_ref[t]
    blk = walk.blk
    first = ki == jnp.maximum(qi - walk.behind, 0)

    def step(tile: Tile):
        s = _strip(tile.cuts, blk)
        cuts, opened = tile.cuts, not tile.diagonal
        if tile.diagonal:   # it opens dk/dv: a strip over every column is
            # stored first and the others add to it, else all add to zeros
            cuts = sorted(cuts, key=lambda cut: cut[1:] != (0, blk))
            if cuts[0][1:] != (0, blk):
                dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, jnp.float32)
                dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, jnp.float32)
                opened = True
        for cut in cuts:
            rows, cols, q, k, g, p, ds = _strip_grads(
                cut, s, tile, scale, q_ref, k_ref, v_ref, g_ref, lse_ref,
                delta_ref, mask_ref)
            if fused:
                row_of = pl.ds(pl.multiple_of(qi * blk + cut[0], s), s)
                dq_acc = jnp.where(first, 0.0, dq_acc_ref[row_of, :]) \
                    + jax.lax.dot_general(
                        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                if tile.diagonal:
                    dq_ref[0, 0, row_of, :] = (dq_acc * scale).astype(
                        dq_ref.dtype)
                else:
                    dq_acc_ref[row_of, :] = dq_acc
            dv = jax.lax.dot_general(
                p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if opened:
                dv = dv + dv_acc_ref[cols, :]
                dk = dk + dk_acc_ref[cols, :]
            dv_acc_ref[cols, :] = dv
            dk_acc_ref[cols, :] = dk
            opened = True

        @pl.when(qi == jnp.minimum(ki + walk.behind, walk.n - 1))
        def _finish():
            dk_ref[0, 0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)

    _by_tile(qi, ki, step, walk)


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


def build_fwd_call(walk: Walk, B, H, d_qk, d_v, in_dtype, out_dtype,
                   interpret=False, group=1):
    """The forward ``pallas_call`` of ``walk`` (shared with the chip-compile
    test); ``group`` query heads read one key/value head. It takes
    ``walk.tables(k_outer=False), mask, q, k, v``."""
    blk, L = walk.blk, walk.n * walk.blk
    sp = _specs(blk, d_qk, d_v, group)
    return _call(
        functools.partial(_fwd_kernel, scale=1.0 / (d_qk ** 0.5), walk=walk),
        f"{walk.name}_fwd", (B, H, walk.pairs(k_outer=False).shape[1]),
        [sp["mask"], sp["q"], sp["k"], sp["v"]], [sp["o"], sp["stat"]],
        [jax.ShapeDtypeStruct((B, H, L, d_v), out_dtype),
         jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32)],
        [pltpu.VMEM((blk, d_v), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32),
         pltpu.VMEM((blk, 1), jnp.float32)],
        interpret,
    )


def build_bwd_calls(walk: Walk, B, H, d_qk, d_v, in_dtype, interpret=False,
                    group=1):
    """The backward's ``pallas_call``s: ``(fused,)`` where ``fused_backward``
    says the row's dq fits VMEM, else ``(dq, dk/dv)``. The fused call and the
    dk/dv call take ``tables(k_outer=True), mask, k, v, q, g, lse, delta``; the
    dq call ``tables(k_outer=False), mask, q, k, v, g, lse, delta``. dk and dv
    come out a query head, ``[B, H, L, d]``, whatever ``group`` is."""
    blk, L = walk.blk, walk.n * walk.blk
    sp = _specs(blk, d_qk, d_v, group)
    how = dict(scale=1.0 / (d_qk ** 0.5), walk=walk)
    grid = (B, H, walk.pairs(k_outer=False).shape[1])
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
            functools.partial(_kv_major_kernel, fused=True, **how),
            f"{walk.name}_bwd", grid, kv_major, [dq_row, sp["dk"], sp["dv"]],
            [wide, wide, narrow],
            [pltpu.VMEM((L, d_qk), jnp.float32)] + kv_scratch, interpret,
            vmem_limit_bytes=_DEFAULT_SCOPED_VMEM + resident),)
    dq = _call(
        functools.partial(_dq_kernel, **how), f"{walk.name}_bwd_dq", grid,
        [sp["mask"], sp["q"], sp["k"], sp["v"], sp["o"], sp["stat"],
         sp["stat"]],
        [sp["q"]], [wide], [pltpu.VMEM((blk, d_qk), jnp.float32)], interpret,
    )
    dkv = _call(
        functools.partial(_kv_major_kernel, fused=False, **how),
        f"{walk.name}_bwd_dkv", grid, kv_major, [sp["dk"], sp["dv"]],
        [wide, narrow], kv_scratch, interpret,
    )
    return dq, dkv


def _tally(walk: Walk, rows: int):
    for key, area in zip(("computed", "walked"), walk.area()):
        kernel_forms.count("attn_tiles", key, rows * area)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _core(q, k, v, mask, walk, dtype, interpret):
    return _core_fwd(q, k, v, mask, walk, dtype, interpret)[0]


@jax.named_scope("flash_fwd")
def _core_fwd(q, k, v, mask, walk, dtype, interpret):
    B, H, _, d_qk = q.shape
    _tally(walk, B * H)
    out, lse = build_fwd_call(walk, B, H, d_qk, v.shape[-1], q.dtype, dtype,
                              interpret, H // k.shape[1])(
        *walk.tables(k_outer=False), mask[:, None, :], q, k, v)
    return out, (q, k, v, mask, out, lse)


@jax.named_scope("flash_bwd")
def _core_bwd(walk, dtype, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    B, H, L, d_qk = q.shape
    g = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    group = H // k.shape[1]
    calls = build_bwd_calls(walk, B, H, d_qk, v.shape[-1], q.dtype,
                            interpret, group)
    _tally(walk, B * H * len(calls))
    kv_major = (*walk.tables(k_outer=True), mask[:, None, :], k, v, q, g, lse,
                delta)
    if len(calls) == 1:
        dq, dk, dv = calls[0](*kv_major)
    else:
        dq = calls[0](*walk.tables(k_outer=False), mask[:, None, :], q, k, v,
                      g, lse, delta)[0]
        dk, dv = calls[1](*kv_major)
    if group > 1:       # a key/value head's gradient: the sum over its group
        dk, dv = (jnp.sum(d.reshape(B, H // group, group, L, d.shape[-1]),
                          axis=2, dtype=jnp.float32).astype(d.dtype)
                  for d in (dk, dv))
    return dq, dk, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def attend(q, k, v, mask, walk: Walk, *, dtype=jnp.float32,
           interpret: bool = False):
    """The kernels of ``walk`` over ``[B, L, H, d_qk]`` q, ``[B, L, H_kv,
    d_qk]`` k and ``[B, L, H_kv, d_v]`` v with a ``[B, L]`` key mask (1 =
    real, ``None``: all); returns ``[B, L, H, d_v]`` in ``dtype``."""
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=jnp.int32)
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    out = _core(heads_first(q), heads_first(k), heads_first(v),
                mask.astype(jnp.int32), walk, jnp.dtype(dtype), interpret)
    return jnp.transpose(out, (0, 2, 1, 3))


def causal_attention(q, k, v, mask=None, *, dtype=jnp.float32,
                     interpret: bool = False):
    """``softmax(q k^T / sqrt(d_qk) + causal + key-pad) v`` over
    ``[B, L, H, d_qk]`` q, ``[B, L, H_kv, d_qk]`` k and ``[B, L, H_kv, d_v]``
    v (query head ``h`` reads key/value head ``h // (H / H_kv)``) with a
    ``[B, L]`` key mask (1 = real); returns ``[B, L, H, d_v]`` in ``dtype``."""
    L = q.shape[1]
    blk = pick_block(L)
    walk = causal_walk(L, blk, strip_height(blk))
    return attend(q, k, v, mask, walk, dtype=dtype, interpret=interpret)

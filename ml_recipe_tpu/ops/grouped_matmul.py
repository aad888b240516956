"""The expert layers' grouped matmuls: ``out[r] = rows[r] @ weights[group of
r]`` over rows sorted by group, as Mosaic kernels on a TPU and as
``jax.lax.ragged_dot`` everywhere else (which is also the tests' oracle).

``grouped_matmul(rows, weights, sizes)`` keeps ``ragged_dot``'s contract to
the letter: operands in the compute dtype, products accumulated in float32,
ONE rounding to the operands' dtype at the output; rows past the last group
give zeros and add nothing to any weight gradient; an empty group's weight
gradient is zero. Its backward is two more calls: the forward kernel with the
expert's block read transposed (``grouped_matmul_drows``: no transposed copy
of the weights in HBM) and a kernel that contracts over a group's rows
(``grouped_matmul_dweights``: a float32 ``[tk, tn]`` accumulator in VMEM,
written once a group).

**Tiles from the shapes alone.** The matrix unit is 128 x 128 and every
published width is a multiple of 128, so column tiles DIVIDE the width
(``width_tile``: 896 and 1,792 whole or in 7 x 128, 2,304 in 2 x 1,152 or 3 x
768) and nothing is padded; the contraction of the forward and d-rows calls
stays whole. The row tile (``row_tile``) follows the rows an expert EXPECTS:
small enough that the tiles a group boundary cuts are a small share of the
group's tiles (a cut tile is visited once a group that has rows in it, and
each visit multiplies the whole tile). Small row tiles are affordable because
**an expert's weight block stays in VMEM across that expert's consecutive row
tiles**: the grid is (column tile, visit, ...) with the visits in group
order, so the weights' block index changes only where the group does and
Pallas fetches the block once a group.

**The walk** (``_visits``; after ``jax.experimental.pallas.ops.tpu.megablox``)
is a static number of steps, row tiles + groups - 1, over prefetched tables
``(group, row tile)``: the tiles a group has rows in, group by group. The
forward's spare steps walk the tiles no held row reaches and write zeros
there; the d-weights walk visits an empty group once, to write its zeros.

Which form runs is ``kernel_mode``'s answer, from what the code can see: a
TPU backend, one device or an enclosing ``shard_map`` (GSPMD cannot partition
a Mosaic call), rows for at least what the groups expect (the expert layer's
first chunk; a granule of its overflow loop, which runs about once in a
hundred micro-batches, is not worth its kernels' compile time), widths that
are multiples of 128, a row tile of 128 or more that divides the rows, blocks
within the VMEM budget. Every ``pallas_call`` is
made under ``jax.named_scope("experts")``, forward and backward, so a trace
reader that goes by scope finds the kernels where it found ``_swiglu``.
"""

from __future__ import annotations

import functools
import logging
from fractions import Fraction
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_forms

logger = logging.getLogger(__name__)

_LANES = 128
ROW_TILES = (512, 256, 128, 64, 32, 16, 8)
# an expert's expected rows fill this many row tiles or more, so that the
# tile a boundary cuts is one of five or more; never under a pass of the
# matrix unit (PERF.md section 6, PR 38, has the readings by row tile)
TILES_AN_EXPERT = 4
MIN_ROW_TILE = 128
# bytes of an expert's weight block (two of them are in flight): 2,304 x 896
# in bf16 is 4.1 MB
_WEIGHT_BLOCK = 9 * 2 ** 19
# elements of the d-weights accumulator (float32, beside two output blocks
# in the compute dtype): 1,152 x 896
_ACCUMULATOR = 9 * 2 ** 17
# what a call may ask of the 128 MiB a v5e core has (16 MiB come unasked)
_VMEM_CEILING = 48 * 2 ** 20
_VMEM_SLACK = 6 * 2 ** 20     # the compiler's own temporaries

_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


# -- tiles ---------------------------------------------------------------------


def row_tile(rows: int, expected) -> Optional[int]:
    """The row tile for ``rows`` rows of which an expert expects
    ``expected``: the largest of ``ROW_TILES`` that divides ``rows`` and fits
    ``TILES_AN_EXPERT`` times into ``expected`` (``MIN_ROW_TILE`` where the
    expectation is smaller than that), or ``None``."""
    most = max(Fraction(expected) / TILES_AN_EXPERT, MIN_ROW_TILE)
    return next((t for t in ROW_TILES if t <= most and rows % t == 0), None)


def _dividers(width: int):
    """The multiples of 128 lanes that divide ``width``, largest first."""
    lanes = width // _LANES
    return [_LANES * d for d in range(lanes, 0, -1) if lanes % d == 0]


def width_tile(contraction: int, width: int, itemsize: int) -> Optional[int]:
    """The column tile of a ``[contraction, width]`` weight whose contraction
    stays whole: the largest divider of ``width`` whose block fits
    ``_WEIGHT_BLOCK``, or ``None``."""
    return next((t for t in _dividers(width)
                 if contraction * t * itemsize <= _WEIGHT_BLOCK), None)


def gradient_tiles(k: int, n: int):
    """``(tk, tn)`` of the d-weights call's ``[k, n]`` output: of the
    dividers whose block ``_ACCUMULATOR`` holds, the pair that reads the
    operands least often (the rows ``n / tn`` times, the cotangent ``k /
    tk`` times: the least ``1 / tk + 1 / tn``)."""
    fits = [(tk, tn) for tk in _dividers(k) for tn in _dividers(n)
            if tk * tn <= _ACCUMULATOR]
    return min(fits, key=lambda t: Fraction(1, t[0]) + Fraction(1, t[1]),
               default=None)


def _rows_blocks(tm, c, tw, itemsize):
    """VMEM of a forward / d-rows call's blocks, two buffers each, and the
    float32 product."""
    return 2 * itemsize * (tm * c + c * tw + tm * tw) + 4 * tm * tw


def _weights_blocks(tm, tk, tn, itemsize):
    """VMEM of the d-weights call's blocks, two buffers each, and the
    float32 accumulator."""
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn


def _vmem_bytes(tm, k, n, itemsize):
    """VMEM of the largest of a matmul's three calls."""
    tn, tk = width_tile(k, n, itemsize), width_tile(n, k, itemsize)
    grad = gradient_tiles(k, n)
    if None in (tn, tk, grad):
        return None
    return max(_rows_blocks(tm, k, tn, itemsize),
               _rows_blocks(tm, n, tk, itemsize),
               _weights_blocks(tm, *grad, itemsize))


def refusal(m: int, k: int, n: int, tm: Optional[int], itemsize: int):
    """Why the kernels do not take ``m`` rows of width ``k`` against weights
    ``[k, n]`` at row tile ``tm`` (a string), or ``None`` where they do."""
    if k % _LANES or n % _LANES:
        return f"widths {k} / {n} are not multiples of {_LANES}"
    if tm is None or tm < MIN_ROW_TILE:
        return f"no row tile of {MIN_ROW_TILE} or more divides {m} rows"
    need = _vmem_bytes(tm, k, n, itemsize)
    if need is None or need + _VMEM_SLACK > _VMEM_CEILING:
        return (f"the blocks of widths {k} / {n} at a row tile of {tm} pass "
                f"{_VMEM_CEILING >> 20} MiB of VMEM")
    return None


def kernel_mode(rows, weights, tm: Optional[int], expected):
    """``None`` where ``rows`` [M, K] against ``weights`` [G, K, N], of
    which a group expects ``expected``, run as ``jax.lax.ragged_dot``, else
    the ``interpret`` argument of the Mosaic kernels (``False``: compiled)."""
    if jax.default_backend() != "tpu":
        return None
    if rows.shape[0] < weights.shape[0] * expected:
        # fewer rows than the groups expect: a granule of the expert layer's
        # overflow loop, which about one micro-batch in a hundred runs. A
        # kernel call costs a start 0.15 s of compile (a refused split pays
        # it on EVERY start) and a granule brings 8 of a layer's 14: not
        # worth it (PERF.md section 6, PR 38)
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if jax.device_count() > 1 and not (
            mesh.axis_names and set(mesh.manual_axes) == set(mesh.axis_names)):
        why = "more than one device and no enclosing shard_map"
    else:
        why = refusal(rows.shape[0], rows.shape[1], weights.shape[2], tm,
                      rows.dtype.itemsize)
    if why is not None:
        kernel_forms.log_refusal(
            logger, f"grouped matmul: the Mosaic kernels refuse this call "
                    f"({why}); running jax.lax.ragged_dot.")
        return None
    return False


# -- the walk ------------------------------------------------------------------


def _spanned(offsets, tm: int):
    """Row tiles each group has rows in (0: an empty group)."""
    starts, ends = offsets[:-1], offsets[1:]
    return jnp.where(ends > starts, (ends - 1) // tm - starts // tm + 1, 0)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(sizes, m: int, tm: int, every_group: bool):
    """The walk's prefetched tables: ``(offsets [G + 1], group [S], tile [S],
    n_active [1])``, ``S = m / tm + G - 1`` steps of which the first
    ``n_active`` visit, group by group, the row tiles a group has rows in
    (and, with ``every_group``, an empty group once). The steps after them
    repeat the last one, or (without ``every_group``) take the tiles no held
    row reaches in turn and then repeat the last tile."""
    G, tiles_m = sizes.shape[0], m // tm
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes, dtype=jnp.int32)])
    visits = _spanned(offsets, tm)
    if every_group:
        visits = jnp.maximum(visits, 1)
    upto = jnp.cumsum(visits, dtype=jnp.int32)
    n_active = upto[-1]
    step = jnp.arange(tiles_m + G - 1, dtype=jnp.int32)
    at = jnp.maximum(jnp.minimum(step, n_active - 1), 0)
    group = jnp.minimum(jnp.sum(
        at[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), G - 1)
    tile = jnp.minimum(offsets[:-1] // tm, tiles_m - 1)[group] \
        + at - (upto - visits)[group]
    if not every_group:
        reached = -(-offsets[-1] // tm)
        tile = jnp.where(step < n_active, tile, jnp.minimum(
            reached + step - n_active, tiles_m - 1))
    return offsets, group, tile, n_active[None]


def tile_fill(offsets, tm: int):
    """Held rows over the rows of the row tiles the kernels visit at row
    tile ``tm`` (1.0: no tile is cut by a group boundary or by filler, or no
    row is held), from a plan's ``offsets`` [G + 1] over the whole of expert
    order: chunks are whole row tiles, so the tiles of the chunks are the
    tiles of the whole."""
    visited = (jnp.sum(_spanned(offsets, tm)) * tm).astype(jnp.float32)
    return jnp.where(visited > 0,
                     offsets[-1].astype(jnp.float32)
                     / jnp.maximum(visited, 1.0), 1.0)


def _here(offsets, groups, tiles, s, tm):
    """``(row tile, first row, end row)`` of step ``s``'s group inside its
    tile."""
    group, tile = groups[s], tiles[s]
    return (tile, jnp.maximum(offsets[group], tile * tm),
            jnp.minimum(offsets[group + 1], (tile + 1) * tm))


def _own_rows(shape, tile, tm, lo, hi):
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


# -- kernels -------------------------------------------------------------------


def _rows_kernel(offsets, groups, tiles, n_active, lhs, rhs, out, *, tm,
                 dims):
    """A step of the forward / d-rows call: the tile's rows against the
    step's group's block; a tile the group does not fill keeps what an
    earlier visit wrote, and zeros where there was none."""
    s = pl.program_id(1)
    tile, lo, hi = _here(offsets, groups, tiles, s, tm)
    active = s < n_active[0]
    fresh = (s == 0) | (tiles[jnp.maximum(s - 1, 0)] != tile)

    def product():
        return jax.lax.dot_general(lhs[...], rhs[...], (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    @pl.when(active & (hi - lo == tm))
    def _whole():
        out[...] = product().astype(out.dtype)

    @pl.when(active & (hi - lo < tm))
    def _cut():
        kept = jnp.where(fresh, 0.0, out[...].astype(jnp.float32))
        out[...] = jnp.where(_own_rows(out.shape, tile, tm, lo, hi),
                             product(), kept).astype(out.dtype)

    @pl.when(jnp.logical_not(active) & fresh)
    def _filler():
        out[...] = jnp.zeros_like(out)


def _weights_kernel(offsets, groups, tiles, n_active, lhs, rhs, out, acc, *,
                    tm):
    """A step of the d-weights call: ``acc += lhs_tile^T @ rhs_tile`` over
    the step's group's rows in the tile; zeroed at a group's first step,
    rounded into the group's block at its last."""
    s = pl.program_id(2)
    tile, lo, hi = _here(offsets, groups, tiles, s, tm)
    group = groups[s]
    active = s < n_active[0]
    first = (s == 0) | (groups[jnp.maximum(s - 1, 0)] != group)
    last = (s == n_active[0] - 1) | (
        groups[jnp.minimum(s + 1, pl.num_programs(2) - 1)] != group)

    @pl.when(active & first)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    def add(left, right):
        acc[...] += jax.lax.dot_general(left, right, (_TN, ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(active & (hi - lo == tm))
    def _whole():
        add(lhs[...], rhs[...])

    @pl.when(active & (hi > lo) & (hi - lo < tm))
    def _cut():
        def own(ref):       # through f32: the v5e selects no packed bf16
            rows = jnp.where(_own_rows(ref.shape, tile, tm, lo, hi),
                             ref[...].astype(jnp.float32), 0.0)
            return rows.astype(ref.dtype)

        add(own(lhs), own(rhs))

    @pl.when(active & last)
    def _store():
        out[...] = acc[...].astype(out.dtype)


def _params(need_bytes, grid_rank):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * grid_rank,
        vmem_limit_bytes=min(need_bytes + _VMEM_SLACK, _VMEM_CEILING))


# The calls are jitted so that a step program traces and lowers each distinct
# one ONCE (an expert layer makes 14 of them, a trunk of four layers 56 of 12
# kinds; a ``pallas_call`` is traced and lowered anew wherever it stands, 45 ms
# each: PERF.md section 6, PR 38); XLA inlines them.
@functools.partial(jax.jit, static_argnames=(
    "tm", "transposed", "name", "interpret"))
def _rows_call(lhs, weights, walk, *, tm, transposed: bool, name: str,
               interpret):
    """``lhs`` [M, C] against each group's ``weights`` block: ``[C, W]``
    blocks of ``[G, C, W]``, or with ``transposed`` ``[W, C]`` blocks of
    ``[G, W, C]`` read as their transposes. Returns [M, W]."""
    m, c = lhs.shape
    w = weights.shape[1 if transposed else 2]
    itemsize = lhs.dtype.itemsize
    tw = width_tile(c, w, itemsize)
    steps = walk[1].shape[0]
    if transposed:
        block = pl.BlockSpec((None, tw, c), lambda j, s, o, g, t, n:
                             (g[s], j, 0))
    else:
        block = pl.BlockSpec((None, c, tw), lambda j, s, o, g, t, n:
                             (g[s], 0, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, dims=_NT if transposed
                          else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(w // tw, steps),
            in_specs=[pl.BlockSpec((tm, c), lambda j, s, o, g, t, n:
                                   (t[s], 0)), block],
            out_specs=pl.BlockSpec((tm, tw), lambda j, s, o, g, t, n:
                                   (t[s], j))),
        out_shape=jax.ShapeDtypeStruct((m, w), lhs.dtype),
        interpret=interpret, name=name,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * c * w, transcendentals=0,
            bytes_accessed=itemsize * (m * c * (w // tw) + weights.size
                                       + m * w)),
        compiler_params=_params(_rows_blocks(tm, c, tw, itemsize), 2),
    )(*walk, lhs, weights)


@functools.partial(jax.jit, static_argnames=("tm", "groups", "interpret"))
def _weights_call(lhs, rhs, walk, *, tm, groups: int, interpret):
    """``out[e] = lhs_e^T @ rhs_e`` over each group's rows: ``lhs`` [M, K],
    ``rhs`` [M, N] -> [G, K, N] in ``lhs``'s dtype."""
    (m, k), n = lhs.shape, rhs.shape[1]
    itemsize = lhs.dtype.itemsize
    tk, tn = gradient_tiles(k, n)
    steps = walk[1].shape[0]
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, k // tk, steps),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, s, o, g, t, a:
                             (t[s], i)),
                pl.BlockSpec((tm, tn), lambda j, i, s, o, g, t, a:
                             (t[s], j))],
            out_specs=pl.BlockSpec((None, tk, tn), lambda j, i, s, o, g, t, a:
                                   (g[s], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        interpret=interpret, name="grouped_matmul_dweights",
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=itemsize * (m * k * (n // tn) + m * n * (k // tk)
                                       + groups * k * n)),
        compiler_params=_params(_weights_blocks(tm, tk, tn, itemsize), 3),
    )(*walk, lhs, rhs)


# -- the operator --------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernels(rows, weights, sizes, tm, interpret):
    return _forward(rows, weights, sizes, tm, interpret)[0]


def _forward(rows, weights, sizes, tm, interpret):
    walk = _visits(sizes, rows.shape[0], tm, False)
    kernel_forms.count("grouped_matmul", "kernel")
    with jax.named_scope("experts"):
        out = _rows_call(rows, weights, walk, tm=tm, transposed=False,
                         name="grouped_matmul_fwd", interpret=interpret)
    return out, (rows, weights, sizes, walk)


def _backward(tm, interpret, residuals, g):
    rows, weights, sizes, walk = residuals
    kernel_forms.count("grouped_matmul", "kernel", 2)
    with jax.named_scope("experts"):
        d_rows = _rows_call(g, weights, walk, tm=tm, transposed=True,
                            name="grouped_matmul_drows", interpret=interpret)
        d_weights = _weights_call(
            rows, g, _visits(sizes, rows.shape[0], tm, True), tm=tm,
            groups=weights.shape[0], interpret=interpret)
    return d_rows, d_weights, None


_kernels.defvjp(_forward, _backward)


def grouped_matmul(rows, weights, sizes, expected=None):
    """``rows`` [M, K] sorted by group, ``weights`` [G, K, N] in ``rows``'s
    dtype, ``sizes`` [G] int32 rows of each group (their sum at most M):
    ``[M, N]`` in ``rows``'s dtype, as ``jax.lax.ragged_dot(rows, weights,
    sizes, preferred_element_type=rows.dtype)`` gives it. ``expected``: the
    rows a group expects (static; ``M / G`` where none is given), which the
    row tile follows."""
    m, groups = rows.shape[0], weights.shape[0]
    if expected is None:
        expected = Fraction(m, groups)
    tm = row_tile(m, expected)
    interpret = kernel_mode(rows, weights, tm, expected)
    if interpret is None:
        kernel_forms.count("grouped_matmul", "ragged_dot")
        return jax.lax.ragged_dot(rows, weights, sizes,
                                  preferred_element_type=rows.dtype)
    return _kernels(rows, weights, sizes.astype(jnp.int32), tm, interpret)

"""The expert layer's token-side walks as Mosaic kernels: a token's held rows
are read once each, by their own DMA, and summed or dotted in VMEM.

An expert layer's rows sit in expert order; three of its four token-side
operations go the other way, from a token to the rows it holds:
``combine``'s forward (``out[t] = sum_j w[t, j] * rows[r_tj]``),
``dispatch``'s backward (the same sum without weights, rounded once to the
cotangent's dtype) and ``combine``'s router-weight gradient (``d_w[t, j] =
<g[t], rows[r_tj]>``). ``expert_ffn._rows_to_tokens`` walks them in XLA as
one masked gather of ALL tokens a depth ``j``, each depth adding into a
``[T, H]`` float32 accumulator; here ONE pass reads a token's held rows and
writes its result once.

**Rows a DMA can take one at a time.** A ``[C, H]`` array in HBM is tiled
eight rows deep, and Mosaic moves whole tiles only. So the rows are first
``pack``ed into 32-bit words, a row's words as ``[H / 128 / p, 1, 128]``
tiles of ONE row each (``p`` values a word: element ``c`` of a bfloat16 row
in a word's low half, ``c + H / 2`` in its high half): one XLA pass over the
rows. The forward ``combine`` keeps the packed rows as its residual in
place of the rows, so its weight gradient reads them as they are.

**The walk.** The grid is over blocks of ``tb`` tokens, in order. A block's
row indices (``slot_row``, held slots first) and its tokens' held counts sit
in SMEM as per-block ``BlockSpec``s (the whole ``[T, K]`` table is beyond
SMEM), with the NEXT block's beside them: while a block is summed, the next
block's rows are on their way, one DMA a held row, into the other half of a
VMEM buffer ``[2, K, tb / 8, words / 128, 8, 1, 128]``, whose one-row tiles
lie in memory as ``(8, 128)`` tiles of eight tokens would: read through a
``[rows, 128]`` view, depth ``j`` of eight tokens is one dense vector load a
128 words. A DMA costs the scalar unit about 50 ns however few bytes it
moves, so the walk is bound by the DMAs it issues; waits are taken eight
rows at a time. Every depth of a group of eight tokens is summed, a slot
past a token's held count masked (its buffer row holds whatever an earlier
block left there): a loop whose trip count is data costs more (PERF.md
section 6 has the readings of each choice).

**Two bodies over that walk.** ``%token_rows_sum`` adds the depths in
ascending ``j`` in float32, the order ``_rows_to_tokens`` adds them in, so
on the chip the two forms agree to the bit; ``%token_rows_dot`` reduces each
depth against ``g`` over the row's width.

Which form runs is ``kernel_mode``'s answer, from what the code can see: a
TPU backend, one device or an enclosing ``shard_map`` (GSPMD cannot
partition a Mosaic call), the expert layer's FIRST chunk (a granule of its
overflow loop keeps the XLA walk, as it keeps ``ragged_dot``: a kernel call
costs every start its compile, PERF.md section 6), bfloat16 or
float32 rows whose words are whole 128s and a token block within the VMEM
budget. The calls are jitted, so a step traces and lowers each distinct one
once.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_forms

logger = logging.getLogger(__name__)

_LANES, _SUBLANES = 128, 8
TOKEN_BLOCKS = (256, 128, 64, 32, 16, 8)
# VMEM of a call's blocks (``_vmem_bytes``), and what the compiler adds
_VMEM_BUDGET = 32 * 2 ** 20
_VMEM_SLACK = 6 * 2 ** 20
# entries of a 1-D int32 block in SMEM: XLA tiles such an array by 1,024
_SMEM_TILE = 1024


def count_xla() -> None:
    """One token-side walk traced in XLA (``expert_ffn._rows_to_tokens``)."""
    kernel_forms.count("token_rows", "xla")


# -- blocks --------------------------------------------------------------------


def _words(width: int, itemsize: int) -> int:
    """32-bit words of a row of ``width`` values."""
    return width * itemsize // 4


def _vmem_bytes(tb: int, depth: int, width: int, itemsize: int) -> int:
    """VMEM of a call at token block ``tb``: the row buffer's two halves,
    two float32 ``[tb, width]`` blocks twice buffered (the output, and ``g``
    or an accumulator) and the held counts' block."""
    return (2 * depth * tb * width * itemsize + 2 * 2 * tb * width * 4
            + 2 * tb * _LANES * 4)


def token_block(tokens: int, depth: int, width: int,
                itemsize: int) -> Optional[int]:
    """The largest of ``TOKEN_BLOCKS`` that divides ``tokens`` and whose
    blocks fit ``_VMEM_BUDGET``, or ``None``."""
    return next((tb for tb in TOKEN_BLOCKS if tokens % tb == 0
                 and _vmem_bytes(tb, depth, width, itemsize) <= _VMEM_BUDGET),
                None)


def refusal(tokens: int, depth: int, width: int, dtype) -> Optional[str]:
    """Why the kernels do not take ``tokens`` tokens of ``depth`` slots over
    rows of ``width`` values of ``dtype`` (a string), or ``None`` where they
    do."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return f"rows of {jnp.dtype(dtype).name}"
    itemsize = jnp.dtype(dtype).itemsize
    if _words(width, itemsize) % _LANES:
        return (f"a row of {width} values is {_words(width, itemsize)} words,"
                f" not whole {_LANES}s")
    if token_block(tokens, depth, width, itemsize) is None:
        return (f"no token block of {TOKEN_BLOCKS[-1]} or more divides "
                f"{tokens} tokens within {_VMEM_BUDGET >> 20} MiB of VMEM at "
                f"{depth} slots of width {width}")
    return None


def kernel_mode(x, slot_row, first: bool):
    """``None`` where rows like ``x`` [*, H] read for ``slot_row`` [T, K]
    take the XLA walk, else the ``interpret`` argument of the kernels
    (``False``: compiled). ``first``: the rows are the expert layer's first
    chunk."""
    if jax.default_backend() != "tpu" or not first:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if jax.device_count() > 1 and not (
            mesh.axis_names and set(mesh.manual_axes) == set(mesh.axis_names)):
        why = "more than one device and no enclosing shard_map"
    else:
        why = refusal(*slot_row.shape, x.shape[-1], x.dtype)
    if why is not None:
        kernel_forms.log_refusal(
            logger, f"token rows: the Mosaic kernels refuse this call "
                    f"({why}); running the XLA walk.")
        return None
    return False


# -- packing -------------------------------------------------------------------


def pack(rows):
    """``rows`` [C, H] (bfloat16 or float32) -> uint32 ``[C, words / 128,
    1, 128]``: a bfloat16 row's element ``c`` in word ``c``'s low half and
    ``c + H / 2`` in its high half; a 32-bit row's bits as they are."""
    c, width = rows.shape
    if rows.dtype.itemsize == 4:
        words = jax.lax.bitcast_convert_type(rows, jnp.uint32)
    else:
        half = width // 2

        def bits(part):
            return jax.lax.bitcast_convert_type(
                part, jnp.uint16).astype(jnp.uint32)

        words = (bits(rows[:, half:]) << 16) | bits(rows[:, :half])
    return words.reshape(c, -1, 1, _LANES)


def _unpack(x, per_word: int):
    """The float32 values of ``x`` (uint32 words): ``[low, high]`` halves of
    bfloat16 pairs, or the word itself."""
    if per_word == 1:
        return [jax.lax.bitcast_convert_type(x, jnp.float32)]
    return [jax.lax.bitcast_convert_type(x << 16, jnp.float32),
            jax.lax.bitcast_convert_type(x & jnp.uint32(0xFFFF0000),
                                         jnp.float32)]


# -- the walk ------------------------------------------------------------------


def _smem_block(total: int, each: int) -> int:
    """Entries of the SMEM block that holds a token block's ``each``
    entries of ``total``: whole 1,024s, or the whole array where it is
    smaller."""
    return min(total, max(each, _SMEM_TILE))


class _Walk(NamedTuple):
    """The static shape of a call's walk."""
    tb: int             # tokens a block
    depth: int          # slots a token (K)
    blocks: int         # 128-word blocks of a packed row
    per_word: int       # values a word
    steps: int          # token blocks
    idx_per: int        # token blocks an SMEM block of indices holds
    held_per: int       # token blocks an SMEM block of held counts holds

    def start(self, i, ahead: int, each: int, per: int):
        """Where block ``i + ahead``'s entries start in its SMEM block."""
        return jnp.minimum(i + ahead, self.steps - 1) % per * each


def _plan(tokens: int, depth: int, width: int, itemsize: int,
          tb: int) -> _Walk:
    total = tokens * depth
    return _Walk(tb, depth, _words(width, itemsize) // _LANES, 4 // itemsize,
                 tokens // tb, _smem_block(total, tb * depth) // (tb * depth),
                 _smem_block(tokens, tb) // tb)


def _smem_specs(walk: _Walk):
    """This token block's and the next one's indices and held counts."""
    def spec(each, per, ahead):
        return pl.BlockSpec(
            (each * per,),
            lambda i: (jnp.minimum(i + ahead, walk.steps - 1) // per,),
            memory_space=pltpu.SMEM)

    each = walk.tb * walk.depth
    return [spec(each, walk.idx_per, 0), spec(each, walk.idx_per, 1),
            spec(walk.tb, walk.held_per, 0), spec(walk.tb, walk.held_per, 1)]


def _walk(smem, rows_hbm, buf, sem, walk: _Walk):
    """Starts the next block's row DMAs (at the first step, this block's
    too), waits for this block's; returns the half of ``buf`` that holds
    this block."""
    idx, idx_next, held, held_next = smem
    i = pl.program_id(0)
    half = i % 2
    each = walk.tb * walk.depth

    def fetch(idx, at, held, held_at, into):
        def token(t, carry):
            # shifts, not the scalar unit's signed division
            group = jax.lax.shift_right_logical(t, 3)
            sublane = jnp.bitwise_and(t, _SUBLANES - 1)
            first = at + t * walk.depth

            def row(j, carry):
                pltpu.make_async_copy(
                    rows_hbm.at[idx[first + j]],
                    buf.at[into, j, group, :, sublane], sem.at[into]).start()
                return carry

            return jax.lax.fori_loop(0, held[held_at + t], row, carry)

        jax.lax.fori_loop(0, walk.tb, token, 0)

    held_at = walk.start(i, 0, walk.tb, walk.held_per)

    @pl.when(i == 0)
    def _first():
        fetch(idx, walk.start(i, 0, each, walk.idx_per), held, held_at, 0)

    @pl.when(i + 1 < walk.steps)
    def _next():
        fetch(idx_next, walk.start(i, 1, each, walk.idx_per), held_next,
              walk.start(i, 1, walk.tb, walk.held_per), 1 - half)

    # a DMA semaphore counts bytes: wait for this block's rows eight at a
    # time (a wait instruction a row costs a fifth of the walk), then the
    # rest one by one; a wait's descriptor only gives the bytes, as the
    # input's first ``size`` rows
    rows = jax.lax.fori_loop(0, walk.tb, lambda t, n: n + held[held_at + t],
                             jnp.int32(0))

    def wait(size):
        def one(k, carry):
            some = rows_hbm.at[pl.ds(0, size)]
            pltpu.make_async_copy(some, some, sem.at[half]).wait()
            return carry

        return one

    # a chunk of fewer than eight rows (a model's example at init) holds
    # fewer than eight a block: they are all the rest
    if rows_hbm.shape[0] >= _SUBLANES:
        jax.lax.fori_loop(0, jax.lax.shift_right_logical(rows, 3),
                          wait(_SUBLANES), 0)
    jax.lax.fori_loop(0, jnp.bitwise_and(rows, _SUBLANES - 1), wait(1), 0)
    return half


def _groups(half, buf, walk: _Walk, body):
    """``body(t0, rows_at)`` over the block's groups of eight tokens;
    ``rows_at(j, b)``: the ``[8, 128]`` words of depth ``j`` and word block
    ``b`` of the group. Every depth is walked: masking the slots past a
    token's rows costs less than a loop whose trip count is data (PERF.md
    section 6)."""
    view = buf.reshape(-1, _LANES)

    def group(g, carry):
        def rows_at(j, b):
            at = (((half * walk.depth + j) * (walk.tb // _SUBLANES) + g)
                  * walk.blocks + b) * _SUBLANES
            return view[pl.ds(pl.multiple_of(at, _SUBLANES), _SUBLANES), :]

        body(pl.multiple_of(g * _SUBLANES, _SUBLANES), rows_at)
        return carry

    jax.lax.fori_loop(0, walk.tb // _SUBLANES, group, 0)


def _sum_kernel(idx, idx_next, held, held_next, held_v, weights, rows_hbm,
                out, buf, sem, acc, *, walk: _Walk, weighted: bool):
    half = _walk((idx, idx_next, held, held_next), rows_hbm, buf, sem, walk)
    dst = out if out.dtype == jnp.float32 else acc
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, walk.depth), 1)

    def body(t0, rows_at):
        n = held_v[pl.ds(t0, _SUBLANES), :]             # [8, 1]
        w = weights[pl.ds(t0, _SUBLANES), :]            # [8, K]
        parts = [jnp.zeros((_SUBLANES, _LANES), jnp.float32)] * (
            walk.per_word * walk.blocks)
        for j in range(walk.depth):
            ok = j < n
            if weighted:
                wj = jnp.sum(jnp.where(lanes == j, w, 0.0), axis=1,
                             keepdims=True)
            for b in range(walk.blocks):
                for k, value in enumerate(_unpack(rows_at(j, b),
                                                  walk.per_word)):
                    # as ``_rows_to_tokens`` has it: masked, then weighed
                    value = jnp.where(ok, value, 0.0)
                    if weighted:
                        value = value * wj
                    at = k * walk.blocks + b
                    parts[at] = parts[at] + value
        for at, part in enumerate(parts):
            dst[pl.ds(t0, _SUBLANES), pl.ds(at * _LANES, _LANES)] = part

    _groups(half, buf, walk, body)
    if dst is not out:
        out[...] = acc[...].astype(out.dtype)


def _dot_kernel(idx, idx_next, held, held_next, held_v, g, rows_hbm, out,
                buf, sem, *, walk: _Walk):
    half = _walk((idx, idx_next, held, held_next), rows_hbm, buf, sem, walk)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, walk.depth), 1)

    def body(t0, rows_at):
        n = held_v[pl.ds(t0, _SUBLANES), :]             # [8, 1]
        gs = [g[pl.ds(t0, _SUBLANES), pl.ds(at * _LANES, _LANES)]
              for at in range(walk.per_word * walk.blocks)]
        d_w = jnp.zeros((_SUBLANES, walk.depth), jnp.float32)
        for j in range(walk.depth):
            s = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
            for b in range(walk.blocks):
                for k, value in enumerate(_unpack(rows_at(j, b),
                                                  walk.per_word)):
                    s = s + gs[k * walk.blocks + b] * value
            s = jnp.sum(s, axis=1, keepdims=True)       # [8, 1]
            d_w = jnp.where((lanes == j) & (j < n), s, d_w)
        out[pl.ds(t0, _SUBLANES), :] = d_w

    _groups(half, buf, walk, body)


def _specs(walk: _Walk, dense_in):
    """The in-specs of a call (``dense_in``: the block spec of its ``[T, *]``
    operand) and its scratch: the row buffer and its two semaphores."""
    return ([*_smem_specs(walk),
             pl.BlockSpec((walk.tb, 1), lambda i: (i, 0)), dense_in,
             pl.BlockSpec(memory_space=pl.ANY)],
            [pltpu.VMEM((2, walk.depth, walk.tb // _SUBLANES, walk.blocks,
                         _SUBLANES, 1, _LANES), jnp.uint32),
             pltpu.SemaphoreType.DMA((2,))])


def _params(need_bytes):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=need_bytes + _VMEM_SLACK)


@functools.partial(jax.jit, static_argnames=(
    "width", "itemsize", "out_dtype", "tb", "interpret"))
def _sum_call(packed, slot_row, held, weights, *, width, itemsize, out_dtype,
              tb, interpret):
    (tokens, depth) = slot_row.shape
    walk = _plan(tokens, depth, width, itemsize, tb)
    weighted = weights is not None
    if weights is None:     # a [T, K] block the body never reads
        weights = jnp.zeros((tokens, depth), jnp.float32)
    in_specs, scratch = _specs(walk, pl.BlockSpec((tb, depth),
                                                  lambda i: (i, 0)))
    flat = slot_row.reshape(-1)
    return pl.pallas_call(
        functools.partial(_sum_kernel, walk=walk, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tokens // tb,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tb, width), lambda i: (i, 0)),
            scratch_shapes=scratch + [pltpu.VMEM((tb, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, width), out_dtype),
        interpret=interpret, name="token_rows_sum",
        cost_estimate=pl.CostEstimate(
            flops=2 * tokens * depth * width, transcendentals=0,
            bytes_accessed=itemsize * tokens * depth * width
            + jnp.dtype(out_dtype).itemsize * tokens * width),
        compiler_params=_params(_vmem_bytes(tb, depth, width, itemsize)),
    )(flat, flat, held, held, held[:, None], weights, packed)


@functools.partial(jax.jit, static_argnames=(
    "width", "itemsize", "tb", "interpret"))
def _dot_call(g, packed, slot_row, held, *, width, itemsize, tb, interpret):
    (tokens, depth) = slot_row.shape
    walk = _plan(tokens, depth, width, itemsize, tb)
    in_specs, scratch = _specs(walk, pl.BlockSpec((tb, width),
                                                  lambda i: (i, 0)))
    flat = slot_row.reshape(-1)
    return pl.pallas_call(
        functools.partial(_dot_kernel, walk=walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tokens // tb,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tb, depth), lambda i: (i, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((tokens, depth), jnp.float32),
        interpret=interpret, name="token_rows_dot",
        cost_estimate=pl.CostEstimate(
            flops=2 * tokens * depth * width, transcendentals=0,
            bytes_accessed=itemsize * tokens * depth * width
            + 4 * tokens * width),
        compiler_params=_params(_vmem_bytes(tb, depth, width, itemsize)),
    )(flat, flat, held, held, held[:, None], g.astype(jnp.float32), packed)


# -- the operations ------------------------------------------------------------


def token_rows_sum(packed, slot_row, held, weights=None, *, width, dtype,
                   out_dtype, interpret=False, tb=None):
    """``out[t] = sum_{j < held[t]} w[t, j] * rows[slot_row[t, j]]`` in
    float32, ``j`` ascending, rounded once to ``out_dtype``; ``packed``:
    ``pack(rows)`` of rows of ``width`` values of ``dtype``; without
    ``weights`` (``None``) ``w`` is 1. ``slot_row`` [T, K] int32, a token's
    ``held`` [T] first. Returns [T, width]."""
    kernel_forms.count("token_rows", "kernel")
    itemsize = jnp.dtype(dtype).itemsize
    tb = tb or token_block(*slot_row.shape, width, itemsize)
    return _sum_call(packed, slot_row.astype(jnp.int32),
                     held.astype(jnp.int32), weights, width=width,
                     itemsize=itemsize, out_dtype=jnp.dtype(out_dtype), tb=tb,
                     interpret=interpret)


def token_rows_dot(g, packed, slot_row, held, *, dtype, interpret=False,
                   tb=None):
    """``d_w[t, j] = <g[t], rows[slot_row[t, j]]>`` in float32 for ``j <
    held[t]``, zero past it; ``g`` [T, H], ``packed``: ``pack(rows)``.
    Returns [T, K]."""
    kernel_forms.count("token_rows", "kernel")
    itemsize = jnp.dtype(dtype).itemsize
    width = g.shape[1]
    tb = tb or token_block(*slot_row.shape, width, itemsize)
    return _dot_call(g, packed, slot_row.astype(jnp.int32),
                     held.astype(jnp.int32), width=width, itemsize=itemsize,
                     tb=tb, interpret=interpret)

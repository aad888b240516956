"""Dropless routed experts over the share of them this process holds.

An expert layer under expert parallelism routes every token over ALL experts
and computes the part of the result its own experts give. Here that part:
the assignments (token, slot) whose expert is held are sorted by expert, the
tokens' rows gathered into that order (``dispatch``), pushed through the
experts' SwiGLU as two grouped matmuls (``experts``:
``ops/grouped_matmul.py``, which on a TPU runs them and their four gradients
as Mosaic kernels whose tiles divide the widths and whose row tile follows the
rows an expert expects, and as ``jax.lax.ragged_dot`` elsewhere), and summed
back per token under the router's weights (``combine``).

**Dropless, with device work that follows the assignments held.** The rows
are processed in chunks of two static sizes, both from the number of held
assignments a micro-batch EXPECTS under uniform routing, ``T x K x held /
all``. The FIRST chunk (``capacity``) is ``FIRST_CHUNK_MARGIN`` (1.5) times
the expectation: a routing near its expectation fits it with a third of its
rows filler, where every operation between token order and expert order runs
over the whole chunk. Every FURTHER chunk is a ``granule``,
``GRANULE_SHARE`` (a quarter) of the expectation, so that a routing over the
first chunk gathers and multiplies a granule's rows and not a chunk's. Both
are rounded up to whole row tiles (``chunk_sizes``). The granules go round a
loop whose trip count is read from the routing itself, up to the worst case
of every token choosing held experts only; nothing is ever dropped, and no
buffer or matmul is sized for that worst case. Only the first chunk keeps
residuals for the backward pass; a granule is recomputed there (the loop's
trip count is data, so reverse-mode cannot unroll it). A token's held rows
may lie in two chunks: its f32 sum then adds the chunks' parts in turn.

A trip round the loop is not cheap however few rows it takes: each one walks
the depths of ``_rows_to_tokens`` over all ``T`` tokens, forward, recomputed
and backward, and adds a whole set of expert weight gradients (measured: 7 ms
a granule where a first chunk's whole layer costs 11, PERF.md section 6,
PR 32). So the margin is set where a skewed router (assignments
held over expected 0.58-1.63 a layer and micro-batch) overflows in about one
micro-batch of a hundred; below that the loop costs more than the filler.

Everything that crosses between token order and sorted order is a row GATHER
in both directions: XLA's scatter-add serialises on a TPU. Towards expert
order (``dispatch``'s forward, ``combine``'s row gradient) it is one gather in
row order. Towards token order a token gathers its held slots, three times a
chunk: ``combine``'s forward, ``dispatch``'s backward and ``combine``'s
router-weight gradient. On the FIRST chunk on a TPU each is one pass of
``ops/token_rows.py``'s kernels over every token's held rows, read once each
(the rows packed first so that a DMA can take one; the forward keeps its
packed rows for the weight gradient). A granule, and every chunk off the TPU,
takes ``_rows_to_tokens``: one masked gather of ALL ``T`` tokens a depth,
added into a float32 accumulator, no deeper than some token goes (most tokens
hold two or three rows); it is also the kernels' oracle.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from fractions import Fraction
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import token_rows
from .grouped_matmul import grouped_matmul, row_tile, tile_fill

# rows of the first chunk and of every further one, in expected held
# assignments of a micro-batch: one pair for every configuration (PERF.md
# section 6, PR 32, has the distribution they were read from)
FIRST_CHUNK_MARGIN = Fraction(3, 2)
GRANULE_SHARE = Fraction(1, 4)
# both in whole row tiles: the grouped matmuls' 512 rows once the expectation
# is ROW_TILE_FROM rows or more, a sublane's 8 below that
ROW_TILE, SMALL_ROW_TILE, ROW_TILE_FROM = 512, 8, 2048


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["order", "position", "held", "row_weight", "offsets",
                 "n_held"], meta_fields=["capacity", "granule"])
@dataclasses.dataclass(frozen=True)
class RoutingPlan:
    """Integers only: which slot of which token sits where in expert order."""

    order: jax.Array        # [A] slot ids (token * K + k), held ones first,
    #                         by expert (A: T * K, filled to ``capacity``
    #                         plus whole granules)
    position: jax.Array     # [T, K] where a slot sits in ``order``
    held: jax.Array         # [T, K] bool: the slot's expert is held here
    row_weight: jax.Array   # [A] the router's weight of ``order``'s slots
    offsets: jax.Array      # [E_held + 1] row at which each expert starts
    n_held: jax.Array       # [] assignments held
    capacity: int           # rows of the first chunk (static)
    granule: int            # rows of every further chunk (static)


def chunk_sizes(tokens: int, top_k: int, count: int, of: int) -> tuple:
    """``(capacity, granule)``: rows of the first chunk and of every further
    one, from the assignments ``tokens`` tokens expect to make to ``count``
    held experts ``of`` all; whole row tiles, and no more than they can."""
    slots = tokens * top_k
    expected = Fraction(slots * count, of)
    tile = ROW_TILE if expected >= ROW_TILE_FROM else SMALL_ROW_TILE

    def rows(share):
        return min(slots, -(-math.ceil(share * expected) // tile) * tile)

    return rows(FIRST_CHUNK_MARGIN), rows(GRANULE_SHARE)


def make_plan(chosen, weights, first: int, count: int, of: int) -> RoutingPlan:
    """``chosen`` [T, K] expert ids over all ``of`` experts, ``weights``
    [T, K]; ``first`` / ``count``: the experts held."""
    T, K = chosen.shape
    local = chosen.astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    slots = jnp.arange(T * K, dtype=jnp.int32)
    _, order, row_weight = jax.lax.sort(
        (key, slots, jax.lax.stop_gradient(weights).reshape(-1)
         .astype(jnp.float32)), num_keys=2)
    position = jnp.argsort(order).astype(jnp.int32).reshape(T, K)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :], axis=0,
        dtype=jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes, dtype=jnp.int32)])
    capacity, granule = chunk_sizes(T, K, count, of)
    filler = -(T * K - capacity) % granule  # the last granule is sliced whole
    if filler:
        order = jnp.pad(order, (0, filler))
        row_weight = jnp.pad(row_weight, (0, filler))
    return RoutingPlan(order, position, held, row_weight, offsets,
                       offsets[-1], capacity, granule)


class _Chunk(NamedTuple):
    token: jax.Array        # [C] the token of each row
    valid: jax.Array        # [C] bool: the row is an assignment, not filler
    row_weight: jax.Array   # [C]
    sizes: jax.Array        # [E_held] rows of each expert inside the chunk
    slot_row: jax.Array     # [T, K] a token's rows in this chunk, first
    slot_ok: jax.Array      # [T, K]   its ``slot_ok`` slots, rest filler
    slot_pick: jax.Array    # [T, K, K] one-hot: compacted slot j is slot k
    depth: jax.Array        # [K] bool: some token has more than j rows here


def _chunk_of(plan: RoutingPlan, lo, C: int) -> _Chunk:
    """The ``C`` rows of expert order from row ``lo`` on."""
    T, K = plan.position.shape
    slots = jax.lax.dynamic_slice_in_dim(plan.order, lo, C)
    rows = lo + jnp.arange(C, dtype=jnp.int32)
    valid = rows < plan.n_held
    clipped = jnp.clip(plan.offsets, lo, lo + C)
    here = plan.held & (plan.position >= lo) & (plan.position < lo + C)
    # a token's slots that lie in this chunk, moved to the front
    front = jnp.argsort(~here, axis=-1, stable=True)
    take = lambda a: jnp.take_along_axis(a, front, axis=-1)  # noqa: E731
    slot_ok = take(here)
    return _Chunk(
        token=slots // K, valid=valid,
        row_weight=jax.lax.dynamic_slice_in_dim(plan.row_weight, lo, C),
        sizes=clipped[1:] - clipped[:-1],
        slot_row=jnp.clip(take(plan.position) - lo, 0, C - 1),
        slot_ok=slot_ok,
        slot_pick=front[:, :, None] == jnp.arange(K)[None, None, :],
        depth=jnp.any(slot_ok, axis=0),
    )


def _rows_to_tokens(rows, chunk: _Chunk, weights=None):
    """``out[t] = sum_j ok[t, j] * w[t, j] * rows[slot_row[t, j]]`` in f32,
    a gather a depth, and no deeper than some token goes."""
    T, K = chunk.slot_row.shape
    acc = jnp.zeros((T, rows.shape[-1]), jnp.float32)
    for j in range(K):
        def add(acc, j=j):
            part = jnp.where(chunk.slot_ok[:, j, None],
                             rows[chunk.slot_row[:, j]], 0)
            part = part.astype(jnp.float32)
            if weights is not None:
                part = part * weights[:, j, None]
            return acc + part

        acc = jax.lax.cond(chunk.depth[j], add, lambda acc: acc, acc)
    return acc


def _held(chunk: _Chunk):
    """[T] a token's rows in the chunk (its ``slot_ok`` slots come first)."""
    return jnp.sum(chunk.slot_ok, axis=-1, dtype=jnp.int32)


# ``mode`` of the three operations below: ``None`` (the XLA walk of
# ``_rows_to_tokens``), else ``(interpret, dtype)``: the token-side walks as
# ``ops/token_rows.py``'s kernels over rows of ``dtype``


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, chunk: _Chunk, mode=None):
    return jnp.where(chunk.valid[:, None], x[chunk.token], 0)


def _dispatch_fwd(x, chunk, mode):
    return _dispatch(x, chunk, mode), chunk


def _dispatch_bwd(mode, chunk, g):
    if mode is None:
        token_rows.count_xla()
        return _rows_to_tokens(g, chunk).astype(g.dtype), None
    interpret, dtype = mode
    return token_rows.token_rows_sum(
        token_rows.pack(g), chunk.slot_row, _held(chunk), width=g.shape[1],
        dtype=dtype, out_dtype=g.dtype, interpret=interpret), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, weights, chunk: _Chunk, mode=None):
    """Tokens' weighted sums of their rows; ``weights`` [T, K] compacted."""
    token_rows.count_xla()
    return _rows_to_tokens(rows, chunk, weights)


def _combine_fwd(rows, weights, chunk, mode):
    if mode is None:
        return _combine(rows, weights, chunk, mode), (rows, chunk)
    # the kernels' weight gradient reads the rows packed: kept so, in place
    # of the rows
    interpret, dtype = mode
    packed = token_rows.pack(rows)
    return token_rows.token_rows_sum(
        packed, chunk.slot_row, _held(chunk), weights.astype(jnp.float32),
        width=rows.shape[1], dtype=dtype, out_dtype=jnp.float32,
        interpret=interpret), (packed, chunk)


def _combine_bwd(mode, residuals, g):
    rows, chunk = residuals
    dtype = rows.dtype if mode is None else mode[1]
    d_rows = jnp.where(
        chunk.valid[:, None],
        g[chunk.token] * chunk.row_weight[:, None], 0).astype(dtype)
    if mode is not None:
        return d_rows, token_rows.token_rows_dot(
            g, rows, chunk.slot_row, _held(chunk), dtype=dtype,
            interpret=mode[0]), None
    token_rows.count_xla()
    T, K = chunk.slot_row.shape
    d_weights = []
    for j in range(K):
        def dot(j=j):
            picked = rows[chunk.slot_row[:, j]].astype(jnp.float32)
            return jnp.where(chunk.slot_ok[:, j],
                             jnp.sum(g * picked, axis=-1), 0.0)

        d_weights.append(jax.lax.cond(
            chunk.depth[j], dot, lambda: jnp.zeros((T,), jnp.float32)))
    return d_rows, jnp.stack(d_weights, axis=-1), None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.checkpoint
def _swiglu(hidden):
    """``silu(gate) * up`` of ``[gate | up]`` rows, in f32; recomputed in the
    backward pass and not kept."""
    gate, up = jnp.split(hidden, 2, axis=-1)
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(hidden.dtype)


def _rows_an_expert(plan: RoutingPlan) -> Fraction:
    """The rows a held expert expects in a micro-batch (what the first chunk
    was sized from): the grouped matmuls' row tile follows it."""
    return plan.capacity / FIRST_CHUNK_MARGIN / (plan.offsets.shape[0] - 1)


def _chunk_result(x, weights, w_gate_up, w_down, plan: RoutingPlan, lo,
                  C: int, first: bool = False):
    """The part of the layer's routed result, [T, H] f32, that the ``C`` rows
    from row ``lo`` on give; ``first``: they are the first chunk."""
    chunk = _chunk_of(plan, lo, C)
    interpret = token_rows.kernel_mode(x, chunk.slot_row, first)
    mode = None if interpret is None else (interpret, jnp.dtype(x.dtype))
    with jax.named_scope("dispatch"):
        rows = _dispatch(x, chunk, mode)
    with jax.named_scope("experts"):
        expected = _rows_an_expert(plan)
        hidden = grouped_matmul(rows, w_gate_up, chunk.sizes, expected)
        act = _swiglu(hidden)
        out = grouped_matmul(act, w_down, chunk.sizes, expected)
    with jax.named_scope("combine"):
        compact = jnp.einsum(
            "tjk,tk->tj", chunk.slot_pick.astype(jnp.float32),
            weights.astype(jnp.float32))
        return _combine(out, compact, chunk, mode)


def _n_chunks(plan: RoutingPlan):
    over = jnp.maximum(0, plan.n_held - plan.capacity)
    return 1 + -(-over // plan.granule)


def _granule_result(x, weights, w_gate_up, w_down, plan: RoutingPlan, c):
    """``_chunk_result`` of chunk ``c`` >= 1: granule ``c - 1``."""
    return _chunk_result(
        x, weights, w_gate_up, w_down, plan,
        plan.capacity + (c - 1) * plan.granule, plan.granule)


@jax.custom_vjp
def routed_experts(x, weights, w_gate_up, w_down, plan: RoutingPlan):
    """``y[t] = sum_{k: chosen[t, k] held} weights[t, k] * Expert(x[t])`` in
    f32. ``x`` [T, H]; ``w_gate_up`` [E_held, H, 2F] (gate then up) and
    ``w_down`` [E_held, F, H] in the compute dtype."""
    return _routed_fwd(x, weights, w_gate_up, w_down, plan)[0]


def _routed_fwd(x, weights, w_gate_up, w_down, plan):
    y, first_vjp = jax.vjp(
        functools.partial(_chunk_result, plan=plan, lo=0, C=plan.capacity,
                          first=True),
        x, weights, w_gate_up, w_down)
    y = jax.lax.fori_loop(
        1, _n_chunks(plan),
        lambda c, y: y + _granule_result(
            x, weights, w_gate_up, w_down, plan, c), y)
    return y, (first_vjp, x, weights, w_gate_up, w_down, plan)


def _routed_bwd(residuals, g):
    first_vjp, x, weights, w_gate_up, w_down, plan = residuals

    def further(c, grads):
        _, vjp = jax.vjp(
            functools.partial(_granule_result, plan=plan, c=c),
            x, weights, w_gate_up, w_down)
        return jax.tree_util.tree_map(jnp.add, grads, vjp(g))

    grads = jax.lax.fori_loop(1, _n_chunks(plan), further, first_vjp(g))
    return (*grads, None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def routing_stats(plan: RoutingPlan) -> dict:
    """What the counters read: assignments held, the fullest held expert over
    the mean of them, the held share of all assignments, the granules taken
    beyond the first chunk, the share of the rows processed that hold no
    assignment, and the held rows over the rows of the row tiles the grouped
    matmuls' kernels visit (1.0: no tile cut by a group boundary or by
    filler; what their tiles would visit where ``ragged_dot`` runs)."""
    sizes = (plan.offsets[1:] - plan.offsets[:-1]).astype(jnp.float32)
    held = plan.n_held.astype(jnp.float32)
    overflow = (_n_chunks(plan) - 1).astype(jnp.float32)
    return {
        "moe_held_assignments": held,
        "moe_load_max_over_mean":
            jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9),
        "moe_held_share": held / plan.position.size,
        "moe_overflow_chunks": overflow,
        "moe_filler_share":
            1.0 - held / (plan.capacity + overflow * plan.granule),
        # chunks are whole row tiles, so the tile that divides both sizes
        # is every chunk's (a tile of one row where none does: nothing cut)
        "moe_row_tile_fill": tile_fill(plan.offsets, row_tile(
            math.gcd(plan.capacity, plan.granule),
            _rows_an_expert(plan)) or 1),
    }

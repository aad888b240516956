"""The chunked gated delta rule (``ops/gated_delta.py`` has the equations) as
a Mosaic kernel family: one program holds a head's ``[d_k, d_v]`` float32
state in VMEM across the chunks of its row, so ``q, k, v, g, beta`` stream in
once and ``o`` streams out once, and nothing a chunk makes on the way (``A``,
the decays, the solve, ``W``, ``U``, ``P``) ever reaches HBM.

The grid is (row, block of heads, chunk) with the chunks innermost and
sequential. A step of the forward (``gated_delta_fwd``), a head at a time:

1. reads the chunk's ``q, k`` ``[C, d_k]`` and ``v`` ``[C, d_v]`` as float32,
   ``c`` (the running sum of ``g`` inside the chunk, made outside: a cumulative
   sum of ``[B, H, L]`` floats) and ``beta``; forms the masked ``exp(c_t -
   c_i)``, ``A`` and ``P`` as the XLA form does: differences inside the chunk
   only, never an ``exp`` of a positive, one ``exp(c_C)`` a chunk boundary;
2. inverts the unit triangle ``I + A`` in VMEM by blocks (``_inverse``);
3. reads the state from scratch and solves the chunk's system with the state
   in its right-hand side, ``U = (I + A)^-1 (diag(beta) V - diag(beta exp c)
   K M)`` (the XLA form, which prepares all chunks before it walks them,
   solves for ``[W | U0]`` and takes ``U0 - W M``: the same ``U``, one
   product more); then ``O`` and the next state as ``_advance`` does; writes
   ``o`` (rounded once) and, where the backward will want it, the state at
   the chunk's start.

The backward (``gated_delta_bwd``) walks the chunks in reverse with ``dM`` in
scratch: it recomputes steps 1-2 from the kept inputs, reads the kept start
state and emits the chunk's ``dq, dk, dv`` and the gradients of ``c`` and
``beta``; the caller turns ``dc`` into ``dg`` (a reversed cumulative sum).

Every product is float32 at HIGHEST, the state, the decays and the inverse
float32: the XLA form's precision, operation for operation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads a grid step, in order of preference: their chains of small products
# are independent, so the scheduler fills one head's latencies with another's
# work, and an even number inverts its triangles two at a time
_HEADS_A_STEP = (6, 4, 2, 5, 3, 1)
# VMEM the blocks of a call may take, of the 16 MiB a call gets without
# asking (v5e): the rest is the products' temporaries
_BLOCK_BUDGET = 12 * 2 ** 20
_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


def _block_bytes(heads, N, chunk, d_k, d_v, itemsize):
    """VMEM of the backward call's blocks (the larger of the two calls), two
    buffers each, and its scratch; minor dimensions padded to 128 lanes."""
    lanes = lambda n: -(-n // 128) * 128    # noqa: E731
    tokens = chunk * itemsize * (4 * lanes(d_k) + 3 * lanes(d_v))
    rows = 4 * -(-N // 8) * 8 * lanes(chunk) * 4    # c, beta, dc, dbeta
    state = d_k * lanes(d_v) * 4
    return heads * (2 * (tokens + rows + state) + state)


def heads_a_step(H, N, chunk, d_k, d_v, itemsize):
    """The heads a grid step takes: the first of ``_HEADS_A_STEP`` that
    divides ``H`` and whose blocks fit ``_BLOCK_BUDGET`` (a row's ``[N, C]``
    floats stay in VMEM, so a long row takes fewer heads), or ``None``."""
    return next((n for n in _HEADS_A_STEP if H % n == 0 and _block_bytes(
        n, N, chunk, d_k, d_v, itemsize) <= _BLOCK_BUDGET), None)


def refusal(H, L, d_k, d_v, chunk, itemsize):
    """Why the kernels do not take ``H`` heads of these widths over rows of
    ``L`` tokens (a whole number of chunks) at this chunk length (a string),
    or ``None`` where they do: the chunk a power of two of at least 16 tokens
    (the inverse halves it down to pairs; a bf16 tile has 16 rows), both head
    widths multiples of 32 (q, k and v blocks hold whole heads, and a
    product's minor dimension fills a quarter of a lane tile or more), and a
    head's blocks within the VMEM budget (6.6 MiB for six heads of 96 / 192 at
    8,192 tokens; two heads a step from 32,768 tokens, one from 131,072,
    none from 196,608)."""
    if chunk < 16 or chunk & (chunk - 1):
        return f"chunk {chunk} is not a power of two of at least 16"
    if d_k % 32 or d_v % 32:
        return f"head widths {d_k} / {d_v} are not multiples of 32"
    if heads_a_step(H, L // chunk, chunk, d_k, d_v, itemsize) is None:
        return (f"a head's blocks at {L} tokens and widths {d_k} / {d_v} "
                f"pass {_BLOCK_BUDGET >> 20} MiB of VMEM")
    return None


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` ``[C, C]``, ``C`` a
    power of two, or for two of them side by side, ``[C, 2C]`` (the result
    likewise). The inverse of a unit triangle ``[[L11, 0], [L21, L22]]`` is
    ``[[X11, 0], [-X22 L21 X11, X22]]``: level by level from the 2 x 2
    diagonal blocks (whose inverse is a change of sign), ALL blocks of a level
    at once. With ``X`` the block-diagonal inverse at block edge ``s`` and
    ``off`` the lower-left quadrants of the blocks of edge ``2s``, ``X - X
    off X`` is the inverse at ``2s``: ``log2(C) - 1`` dependent levels of two
    products where a row-by-row substitution has ``C`` dependent steps. Two
    heads side by side multiply a ``[2C, 2C]`` block diagonal from the left
    operand's side: 128 columns, a whole tile of the matrix unit, where one
    head's 64 fill half. The inverse is formed whole: a row of it reads
    later rows of ``A`` only as products with exact zeros, so what stands
    right of a row's end is harmless as long as it is finite in float32
    (``|A| <= 2`` for keys of unit length, as the layer makes them)."""
    C, width = A.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    cols = lanes & (C - 1)

    def times(x, y):    # x @ y a head: y's heads down a block diagonal
        if width > C:
            y = jnp.concatenate([jnp.where(lanes < C, y, 0.0),
                                 jnp.where(lanes >= C, y, 0.0)], axis=0)
        return _dot(x, y, _NN)

    X = jnp.where(rows == cols, 1.0, 0.0) - jnp.where(
        (rows >> 1) == (cols >> 1), A, 0.0)
    level = 1
    while (2 << level) <= C:
        quadrant = ((rows >> (level + 1)) == (cols >> (level + 1))) & (
            ((rows >> level) & 1) == 1) & (((cols >> level) & 1) == 0)
        X = X - times(times(X, jnp.where(quadrant, A, 0.0)), X)
        level += 1
    return X


class _Chunk:
    """What a chunk and head make of their inputs before any state enters,
    kept by name for the backward. ``T`` is set by ``_chunks``."""

    def __init__(self, q, k, v, c_row, beta_row):
        C, d_k = q.shape
        self.scale = scale = d_k ** -0.5
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.eye = rows == cols
        self.q, self.k, self.v = q, k, v
        c = self.column(c_row)
        self.beta = beta = self.column(beta_row)
        upto = rows >= cols
        # exp(c_t - c_i) where i <= t, 0 elsewhere (never exp of a positive)
        self.decay = decay = jnp.where(
            upto, jnp.exp(jnp.where(upto, c - c_row, 0.0)), 0.0)
        self.before = before = jnp.where(rows > cols, decay, 0.0)
        self.kk = kk = _dot(k, k, _NT)
        self.qk = qk = _dot(q, k, _NT)
        self.A = beta * before * kk
        self.exp_c = exp_c = jnp.exp(c)
        self.rhs_k = k * (beta * exp_c)
        self.P = decay * qk * scale
        self.q_in = q * (exp_c * scale)
        # [1, C]: the chunk's last token
        self.last = last = jax.lax.broadcasted_iota(
            jnp.int32, (1, C), 1) == C - 1
        c_last = jnp.sum(jnp.where(last, c_row, 0.0), axis=1, keepdims=True)
        self.to_end = to_end = jnp.exp(c_last - c)
        self.k_out = k * to_end
        self.carry = jnp.exp(c_last)        # [1, 1]

    def column(self, row):
        """``[1, C] -> [C, 1]``."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, column):
        """``[C, 1] -> [1, C]``."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0,
                       keepdims=True)

    def written(self, M):
        """``U``, the values the chunk really writes from state ``M``: the
        unit-triangular system with the state in its right-hand side,
        ``(I + A) U = diag(beta) V - diag(beta exp c) K M``."""
        return _dot(self.T, self.v * self.beta - _dot(self.rhs_k, M, _NN),
                    _NN)


def _chunks(q_ref, k_ref, v_ref, c_ref, beta_ref, at, heads):
    """The grid step's ``_Chunk``s, solved: the heads' triangles are
    inverted two at a time where their number is even."""
    found = [_Chunk(*(ref[0, h].astype(jnp.float32)
                      for ref in (q_ref, k_ref, v_ref)),
                    c_ref[0, h, pl.ds(at, 1), :],
                    beta_ref[0, h, pl.ds(at, 1), :]) for h in range(heads)]
    C = found[0].A.shape[0]
    if heads % 2:
        for x in found:
            x.T = _inverse(x.A)
    else:
        for x, y in zip(found[::2], found[1::2]):
            both = _inverse(jnp.concatenate([x.A, y.A], axis=1))
            x.T, y.T = both[:, :C], both[:, C:]
    return found


def _fwd_kernel(q_ref, k_ref, v_ref, c_ref, beta_ref, o_ref, *rest,
                heads: int, keep_states: bool):
    starts_ref, M_ref = rest if keep_states else (None,) + rest
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _zero_state():
        M_ref[...] = jnp.zeros_like(M_ref)

    for h, x in enumerate(_chunks(q_ref, k_ref, v_ref, c_ref, beta_ref, n,
                                  heads)):
        M = M_ref[h]
        if keep_states:
            starts_ref[0, 0, h] = M
        U = x.written(M)
        o_ref[0, h] = (_dot(x.q_in, M, _NN) + _dot(x.P, U, _NN)).astype(
            o_ref.dtype)
        M_ref[h] = x.carry * M + _dot(x.k_out, U, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, c_ref, beta_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dc_ref, dbeta_ref, dM_ref, *,
                heads: int):
    n = pl.program_id(2)
    at = pl.num_programs(2) - 1 - n         # the chunk: the last one first

    @pl.when(n == 0)
    def _zero_state():
        dM_ref[...] = jnp.zeros_like(dM_ref)

    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)    # noqa: E731
    for h, x in enumerate(_chunks(q_ref, k_ref, v_ref, c_ref, beta_ref, at,
                                  heads)):
        M, dM, dO = starts_ref[0, 0, h], dM_ref[h], \
            do_ref[0, h].astype(jnp.float32)
        # the walk's step, transposed
        U = x.written(M)
        dU = _dot(x.P, dO, _TN) + _dot(x.k_out, dM, _NN)
        dq_in = _dot(dO, M, _NT)
        dP = _dot(dO, U, _NT)       # read under the decays' mask only
        dk_out = _dot(U, dM, _NT)
        d_carry = jnp.sum(rowsum(dM * M), axis=0, keepdims=True)
        # the solve, transposed: U = T R with T = (I + A)^-1, so the
        # right-hand side's cotangent is T^T dU and A's is -dR U^T
        dR = _dot(x.T, dU, _TN)
        dA = -_dot(dR, U, _NT)      # read under ``before``'s mask only
        d_rhs_k = -_dot(dR, M, _NT)
        dM_ref[h] = x.carry * dM + _dot(x.q_in, dO, _TN) \
            - _dot(x.rhs_k, dR, _TN)
        # what the chunk made of its inputs, transposed
        write = rowsum(d_rhs_k * x.k)          # d (beta exp c)
        dkk = dA * x.beta * x.before
        dqk = dP * x.decay * x.scale
        # d decay * decay: c enters the decays as c_t (rows) - c_i (columns)
        through = dA * x.beta * x.kk * x.before \
            + dP * x.qk * x.scale * x.decay
        out_c = rowsum(dk_out * x.k_out)
        d_c = x.beta * x.exp_c * write + rowsum(through) \
            + rowsum(dq_in * x.q_in) - out_c
        d_last = jnp.sum(out_c, axis=0, keepdims=True) + d_carry * x.carry
        dc_ref[0, h, pl.ds(at, 1), :] = x.row(d_c) - jnp.sum(
            through, axis=0, keepdims=True) + jnp.where(x.last, d_last, 0.0)
        dbeta_ref[0, h, pl.ds(at, 1), :] = x.row(
            rowsum(dR * x.v) + x.exp_c * write
            + rowsum(dA * x.before * x.kk))
        dv_ref[0, h] = (x.beta * dR).astype(dv_ref.dtype)
        dq_ref[0, h] = (_dot(dqk, x.k, _NN) + dq_in * (x.exp_c * x.scale)
                        ).astype(dq_ref.dtype)
        dk_ref[0, h] = (x.beta * x.exp_c * d_rhs_k + _dot(dkk, x.k, _NN)
                        + _dot(dkk, x.k, _TN) + _dot(dqk, x.q, _TN)
                        + dk_out * x.to_end).astype(dk_ref.dtype)


def _specs(heads, chunk, N, d_k, d_v, reverse):
    """Block specs over the (row, block of heads, chunk) grid; ``reverse``
    walks the chunks last to first."""
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)
    tokens = lambda width: pl.BlockSpec(    # noqa: E731
        (1, heads, chunk, width), lambda b, h, n: (b, h, at(n), 0))
    # a row's [N, C] floats stay in VMEM for the whole row; a step reads, or
    # writes, its chunk's line of them
    a_row = pl.BlockSpec((1, heads, N, chunk), lambda b, h, n: (b, h, 0, 0))
    states = pl.BlockSpec((1, 1, heads, d_k, d_v),
                          lambda b, h, n: (at(n), b, h, 0, 0))
    return tokens(d_k), tokens(d_v), a_row, states


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        name=name, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


def forward(q, k, v, c, beta, *, keep_states: bool, interpret: bool = False):
    """``q, k`` [B, H, L, d_k] and ``v`` [B, H, L, d_v] in the compute dtype,
    ``c`` (the running sum of the log decay inside each chunk) and ``beta``
    [B, H, N, C] float32, ``L = N C``. Returns ``(o [B, H, L, d_v] in q's
    dtype, the state at each chunk's start [N, B, H, d_k, d_v] float32 or
    None)``."""
    B, H, L, d_k = q.shape
    d_v, (N, C) = v.shape[-1], c.shape[2:]
    heads = heads_a_step(H, N, C, d_k, d_v, q.dtype.itemsize)
    wide, narrow, a_row, states = _specs(heads, C, N, d_k, d_v, False)
    out_specs = [narrow] + [states] * keep_states
    out_shape = [jax.ShapeDtypeStruct((B, H, L, d_v), q.dtype)] + [
        jax.ShapeDtypeStruct((N, B, H, d_k, d_v), jnp.float32)] * keep_states
    out = _call(
        functools.partial(_fwd_kernel, heads=heads, keep_states=keep_states),
        "gated_delta_fwd", (B, H // heads, N),
        [wide, wide, narrow, a_row, a_row], out_specs, out_shape,
        [pltpu.VMEM((heads, d_k, d_v), jnp.float32)], interpret,
    )(q, k, v, c, beta)
    return (out[0], out[1]) if keep_states else (out[0], None)


def backward(q, k, v, c, beta, starts, d_out, *, interpret: bool = False):
    """The five gradients, ``(dq, dk, dv`` in their inputs' dtypes, ``dc,
    dbeta`` [B, H, N, C] float32``)``, of ``forward``'s output under the
    cotangent ``d_out`` [B, H, L, d_v], from the inputs and the kept
    states."""
    B, H, L, d_k = q.shape
    d_v, (N, C) = v.shape[-1], c.shape[2:]
    heads = heads_a_step(H, N, C, d_k, d_v, q.dtype.itemsize)
    wide, narrow, a_row, states = _specs(heads, C, N, d_k, d_v, True)
    per_token = jax.ShapeDtypeStruct((B, H, N, C), jnp.float32)
    return _call(
        functools.partial(_bwd_kernel, heads=heads),
        "gated_delta_bwd", (B, H // heads, N),
        [wide, wide, narrow, a_row, a_row, states, narrow],
        [wide, wide, narrow, a_row, a_row],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype), per_token, per_token],
        [pltpu.VMEM((heads, d_k, d_v), jnp.float32)], interpret,
    )(q, k, v, c, beta, starts, d_out)

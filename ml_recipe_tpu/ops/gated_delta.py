"""The gated delta rule of a ``linear_attention`` layer, in chunked form with
a backward pass of its own.

A head of key width ``d_k`` and value width ``d_v`` carries a state
``S [d_v, d_k]`` along its row (``S_0 = 0``, float32):

    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t / sqrt(d_k)

``g_t <= 0`` is the log of the step's decay, ``beta_t`` in (0, 2) the
strength of the write: the old value under key ``k_t`` is taken out and
``v_t`` put in (``beta > 1`` gives the transition a negative eigenvalue).
The code holds ``M = S^T [d_k, d_v]`` so that rows of tokens multiply it from
the left.

**Chunks.** ``M_t = exp(g_t) M_{t-1} + k_t^T u_t`` with ``u_t = beta_t (v_t -
exp(g_t) k_t M_{t-1})`` the value really written. Inside a chunk of ``C``
tokens that starts at state ``M``, with ``c_t`` the running sum of ``g`` from
the chunk's first token, the ``u`` of the chunk solve a unit-lower-triangular
system (the WY / UT transform):

    (I + A) U = diag(beta) V - diag(beta exp(c)) K M
    A[t, i] = beta_t exp(c_t - c_i) (k_t . k_i)        for i < t

so ``U = U0 - W M`` with ``[W | U0] = (I + A)^-1 [diag(beta exp(c)) K |
diag(beta) V]`` (XLA's triangular solve: float32 on the chip, as exact as a
hand-written substitution at HIGHEST; PERF.md section 6, PR 33), and

    O      = (exp(c) Q) M / sqrt(d_k) + P U     P[t, i] = exp(c_t - c_i) (q_t . k_i) / sqrt(d_k), i <= t
    M_next = exp(c_C) M + (exp(c_C - c) K)^T U

Everything that does not read ``M`` (``A``, the solve, ``P``) is computed for
all chunks at once (``_prepare``); the walk over the chunks (``_advance``, a
``lax.scan``) is four small products a chunk. Every exponent is a difference
``c_t - c_i`` with ``i <= t``, so nothing overflows and nothing is divided by
a decay.

**Precision.** ``q``, ``k``, ``v`` arrive in the compute dtype (bf16) and are
read as float32; ``g`` and ``beta`` are float32. The state, the decays, the
solve and every product inside the operator are float32 at HIGHEST; the
output is rounded once to the compute dtype.

**Backward** (a ``custom_vjp``). The forward keeps its five inputs and the
state at each chunk's start (``[N, B, H, d_k, d_v]`` float32), nothing else.
The backward recomputes ``_prepare``, walks the chunks in REVERSE carrying
``dM``, transposing one ``_advance`` a chunk from the kept state, and then
transposes ``_prepare``, for ``HEADS_A_PASS`` heads at a time.

**Two forms, one choice.** On a TPU, for head widths and a chunk length the
Mosaic kernels take and rows whose blocks fit VMEM
(``gated_delta_kernel.refusal``), forward and backward are
``ops/gated_delta_kernel.py``: the same chunks, the same products at the same
precision and the same residuals, with a head's state held in VMEM across
its row's chunks, the solve fused into the walk and no intermediate in HBM,
so the heads need no passes. Everywhere else (another backend, other widths)
it is the plain XLA form below, which is also the tests' oracle. The choice is
``kernel_mode``'s, from the backend and the shapes alone. GSPMD cannot
partition a Mosaic call: under a mesh that shards the batch the layer calls
``over_batch_shards``.

Rows are padded on the right (a padded token has ``k = v = 0`` and ``beta``
whatever: it writes nothing that an earlier token reads, the rule is causal).
Every row is scanned whole from a zero state: state resets at the boundaries
of packed segments, a state carried in from an earlier call and a
single-token decode step are not built (``models/mla_moe.unsupported``
refuses packing by name).
The chip's ``exp`` is 5e-6 off: one ``exp`` a chunk and one product a chunk
boundary keep that under a rounding of the output over 8,192 tokens, where a
decay multiplied in token by token compounds it to percents.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from . import gated_delta_kernel

logger = logging.getLogger(__name__)

CHUNK = 64
# the backward pass takes the heads this many at a time: what it recomputes
# and the cotangents of it, all float32, are live for one share of the heads
# only (3.3 GB for 30 heads of 96 / 192 at 8,192 tokens, a third of it so)
HEADS_A_PASS = 10


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _solve(A, rhs):
    """``(I + A)^-1 rhs`` for ``A`` strictly lower triangular."""
    return jax.lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)


def _chunked(x, chunk):
    """``[B, L, H, ...] -> [N, B, H, C, ...]``."""
    B, L, H = x.shape[:3]
    x = x.reshape((B, L // chunk, chunk, H) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)


def _unchunked(x):
    """``[N, B, H, C, ...] -> [B, L, H, ...]``."""
    N, B, H, C = x.shape[:4]
    return jnp.moveaxis(jnp.moveaxis(x, 0, 2), 1, 3).reshape(
        (B, N * C, H) + x.shape[4:])


def _prepare(q, k, v, g, beta):
    """What the walk needs of every chunk and no state enters: ``(W, U0, P,
    q_in, k_out, carry)``, chunked ``[N, B, H, C, ...]``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    C, d_k = q.shape[-2], q.shape[-1]
    scale = d_k ** -0.5
    c = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(C)
    upto = rows[:, None] >= rows[None, :]
    # exp(c_t - c_i) where i <= t, 0 elsewhere (never exp of a positive)
    decay = jnp.exp(jnp.where(upto, c[..., :, None] - c[..., None, :],
                              -jnp.inf))
    before = jnp.where(rows[:, None] > rows[None, :], decay, 0.0)
    A = beta[..., :, None] * before * _mm("...td,...id->...ti", k, k)
    rhs = jnp.concatenate(
        [k * (beta * jnp.exp(c))[..., None], v * beta[..., None]], axis=-1)
    solved = _solve(A, rhs)
    W, U0 = solved[..., :d_k], solved[..., d_k:]
    P = decay * _mm("...td,...id->...ti", q, k) * scale
    q_in = q * (jnp.exp(c) * scale)[..., None]
    k_out = k * jnp.exp(c[..., -1:] - c)[..., None]
    return W, U0, P, q_in, k_out, jnp.exp(c[..., -1])


def _advance(M, W, U0, P, q_in, k_out, carry):
    """One chunk from state ``M`` [B, H, d_k, d_v]: ``(M_next, O)``."""
    U = U0 - _mm("...tk,...kv->...tv", W, M)
    out = _mm("...tk,...kv->...tv", q_in, M) + _mm("...ti,...iv->...tv", P, U)
    M_next = carry[..., None, None] * M + _mm("...tk,...tv->...kv", k_out, U)
    return M_next, out


def _padded(x, L_pad):
    pad = L_pad - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _forward(q, k, v, g, beta, chunk):
    """``(output [B, L, H, d_v] in q's dtype, the state at each chunk's start
    [N, B, H, d_k, d_v])``."""
    B, L, H, d_k = q.shape
    L_pad = -(-L // chunk) * chunk
    parts = _prepare(*(_chunked(_padded(x, L_pad), chunk)
                       for x in (q, k, v, g, beta)))

    def step(M, chunk_parts):
        M_next, out = _advance(M, *chunk_parts)
        return M_next, (M, out)

    M0 = jnp.zeros((B, H, d_k, v.shape[-1]), jnp.float32)
    _, (starts, out) = jax.lax.scan(step, M0, parts)
    return _unchunked(out)[:, :L].astype(q.dtype), starts


@functools.lru_cache(maxsize=None)
def _log_refusal(why: str) -> None:
    """Once a reason (every layer traces the same shapes)."""
    logger.warning(f"gated delta rule: the Mosaic kernels refuse this shape "
                   f"({why}); running the XLA form.")


def kernel_mode(q, v, chunk: int = CHUNK):
    """``None`` where the operator runs its XLA form on ``q`` [B, L, H, d_k]
    and ``v`` [B, L, H, d_v], else the ``interpret`` argument of the Mosaic
    kernels (``False``: compiled): the kernels on a TPU backend for the
    shapes they take."""
    if jax.default_backend() != "tpu":
        return None
    _, L, H, d_k = q.shape
    why = gated_delta_kernel.refusal(
        H, -(-L // chunk) * chunk, d_k, v.shape[-1], chunk, q.dtype.itemsize)
    if why is not None:
        _log_refusal(why)
        return None
    return False


def _heads_first(x, L_pad):
    """``[B, L, H, d] -> [B, H, L_pad, d]``, the kernels' layout."""
    return jnp.transpose(_padded(x, L_pad), (0, 2, 1, 3))


def _per_chunk(x, L_pad, chunk):
    """``[B, L, H] -> [B, H, N, C]``."""
    return jnp.moveaxis(_padded(x, L_pad), 1, 2).reshape(
        x.shape[0], x.shape[2], L_pad // chunk, chunk)


def _per_token(x, L):
    """``[B, H, N, C] -> [B, L, H]``."""
    return jnp.moveaxis(x.reshape(x.shape[:2] + (-1,)), 1, 2)[:, :L]


def _kernel_operands(q, k, v, g, beta, L_pad, chunk):
    """The kernels' first five operands: q, k and v heads first, the running
    sum of the log decay inside each chunk, and ``beta`` a chunk."""
    return (*(_heads_first(x, L_pad) for x in (q, k, v)),
            jnp.cumsum(_per_chunk(g, L_pad, chunk), axis=-1),
            _per_chunk(beta, L_pad, chunk))


def _kernel_forward(q, k, v, g, beta, chunk, interpret, keep_states):
    """``_forward`` by the Mosaic kernel; the states only where kept."""
    L = q.shape[1]
    out, starts = gated_delta_kernel.forward(
        *_kernel_operands(q, k, v, g, beta, -(-L // chunk) * chunk, chunk),
        keep_states=keep_states, interpret=interpret)
    return jnp.transpose(out, (0, 2, 1, 3))[:, :L], starts


def _kernel_backward(chunk, interpret, q, k, v, g, beta, starts, d_out):
    L = q.shape[1]
    L_pad = starts.shape[0] * chunk
    dq, dk, dv, dc, dbeta = gated_delta_kernel.backward(
        *_kernel_operands(q, k, v, g, beta, L_pad, chunk), starts,
        _heads_first(d_out, L_pad), interpret=interpret)
    # c is g's running sum inside a chunk: dg_t = sum of dc over s >= t
    dg = jnp.flip(jnp.cumsum(jnp.flip(dc, -1), axis=-1), -1)
    return tuple(jnp.transpose(dx, (0, 2, 1, 3))[:, :L]
                 for dx in (dq, dk, dv)) + (
        _per_token(dg, L), _per_token(dbeta, L))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gated_delta(q, k, v, g, beta, chunk, kernel=None):
    """``kernel``: ``kernel_mode``'s answer."""
    with jax.named_scope("gated_delta"):
        if kernel is not None:
            return _kernel_forward(q, k, v, g, beta, chunk, kernel, False)[0]
        return _forward(q, k, v, g, beta, chunk)[0]


def _fwd(q, k, v, g, beta, chunk, kernel):
    with jax.named_scope("gated_delta"):
        if kernel is not None:
            out, starts = _kernel_forward(q, k, v, g, beta, chunk, kernel,
                                          True)
        else:
            out, starts = _forward(q, k, v, g, beta, chunk)
    return out, (q, k, v, g, beta, starts)


def _bwd_heads(chunk, q, k, v, g, beta, starts, d_out):
    """The backward pass over the heads given."""
    L = q.shape[1]
    L_pad = starts.shape[0] * chunk
    inputs = tuple(_chunked(_padded(x, L_pad), chunk)
                   for x in (q, k, v, g, beta))
    parts, prepare_vjp = jax.vjp(_prepare, *inputs)
    d_out = _chunked(_padded(d_out.astype(jnp.float32), L_pad), chunk)

    def step(dM, xs):
        M, chunk_parts, d_chunk = xs
        _, advance_vjp = jax.vjp(_advance, M, *chunk_parts)
        dM, *d_parts = advance_vjp((dM, d_chunk))
        return dM, tuple(d_parts)

    _, d_parts = jax.lax.scan(
        step, jnp.zeros(starts.shape[1:], jnp.float32),
        (starts, parts, d_out), reverse=True)
    return tuple(_unchunked(dx)[:, :L].astype(x.dtype) for dx, x in zip(
        prepare_vjp(d_parts), (q, k, v, g, beta)))


def _bwd(chunk, kernel, residuals, d_out):
    if kernel is not None:
        with jax.named_scope("gated_delta"):
            return _kernel_backward(chunk, kernel, *residuals, d_out)
    q, k, v, g, beta, starts = residuals
    H = q.shape[2]
    passes = H // HEADS_A_PASS if H % HEADS_A_PASS == 0 else 1
    with jax.named_scope("gated_delta"):
        if passes == 1:
            return _bwd_heads(chunk, q, k, v, g, beta, starts, d_out)
        # [.., H, ..] -> [passes, .., H / passes, ..]: a pass's share of the
        # heads, one pass after the other
        share = lambda x: jnp.moveaxis(x.reshape(  # noqa: E731
            x.shape[:2] + (passes, H // passes) + x.shape[3:]), 2, 0)
        grads = jax.lax.map(
            lambda xs: _bwd_heads(chunk, *xs),
            tuple(share(x) for x in (q, k, v, g, beta, starts, d_out)))
        return tuple(jnp.moveaxis(dx, 0, 2).reshape(x.shape)
                     for dx, x in zip(grads, (q, k, v, g, beta)))


_gated_delta.defvjp(_fwd, _bwd)


def gated_delta_rule(q, k, v, g, beta):
    """``q``, ``k`` [B, L, H, d_k] and ``v`` [B, L, H, d_v] in the compute
    dtype, ``g`` (the log decay, <= 0) and ``beta`` [B, L, H] float32;
    returns ``o`` [B, L, H, d_v] in ``q``'s dtype. Every row starts from a
    zero state."""
    return _gated_delta(q, k, v, g.astype(jnp.float32),
                        beta.astype(jnp.float32), CHUNK,
                        kernel_mode(q, v))


def over_batch_shards(mesh, q, k, v, g, beta):
    """``gated_delta_rule`` under ``mesh``: where the Mosaic form runs and
    the mesh shards the batch, once a shard of it (GSPMD cannot partition a
    Mosaic call); otherwise the plain call, which GSPMD partitions or an
    enclosing data island already holds a shard's rows of."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.compat import shard_map
    from .attention import _kernel_shard_axes, _manual_batch_axis

    axes = _kernel_shard_axes(mesh)
    if (axes is None or _manual_batch_axis(mesh) is not None
            or kernel_mode(q, v) is None):
        return gated_delta_rule(q, k, v, g, beta)
    batch_axis, head_axis = axes
    if (q.shape[0] % (mesh.shape[batch_axis] if batch_axis else 1)
            or q.shape[2] % (mesh.shape[head_axis] if head_axis else 1)):
        _log_refusal(f"batch {q.shape[0]} and heads {q.shape[2]} do not "
                     f"divide over the mesh {dict(mesh.shape)}")
        return _gated_delta(q, k, v, g.astype(jnp.float32),
                            beta.astype(jnp.float32), CHUNK, None)
    wide, narrow = P(batch_axis, None, head_axis, None), \
        P(batch_axis, None, head_axis)
    return shard_map(
        gated_delta_rule, mesh=mesh,
        in_specs=(wide, wide, wide, narrow, narrow), out_specs=wide,
        check_vma=False)(q, k, v, g, beta)

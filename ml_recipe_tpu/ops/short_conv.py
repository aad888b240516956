"""The double-gated short convolution of an ``lfm2`` layer, between its two
projections: ``y = Cg * conv(Bg * x)`` over ``[Bg | Cg | x]``, the thirds of
the input projection's output in that order.

``conv`` is depthwise and causal over ``K`` taps: ``c[t, d] = sum_j w[d, j] *
z[t - (K-1) + j, d]`` with ``z`` zero before position 0, so ``w[:, K-1]``
multiplies the current position (a ``Conv1d(groups=D, padding=K-1)`` cut to
its first ``L`` outputs). Nothing later than ``t`` enters ``c[t]``, and rows
are padded on the right, so an attended position never reads a padded one and
the operator takes no mask.

Everything stays in the ``[B, L, D]`` layout the projections produce: the
taps are ``K`` shifted reads along ``L``, with no transpose to channels-first
and no convolution primitive. The elementwise work runs in f32 on the
compute-dtype input and is written once in the compute dtype; the backward
pass (a ``custom_vjp``) keeps only that input and the taps and recomputes the
f32 products, as ``models/mla_moe.py``'s ``_rms_norm`` and ``_swiglu_act``
do: per token and layer it reads ``3D`` and writes ``D`` forward, reads
``3D + D`` and writes ``3D`` backward (44 KB at ``D`` 2,048 in bf16), plus
the taps' own gradient, a reduction over every row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift(z, back: int):
    """``out[:, t] = z[:, t - back]``, zero where ``t < back``."""
    if back == 0:
        return z
    L = z.shape[1]
    return jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :L]


def _advance(z, ahead: int):
    """``out[:, t] = z[:, t + ahead]``, zero past the end."""
    if ahead == 0:
        return z
    return jnp.pad(z, ((0, 0), (0, ahead), (0, 0)))[:, ahead:]


def _thirds(bcx):
    return jnp.split(bcx.astype(jnp.float32), 3, axis=-1)


def _taps(z, w):
    """The causal depthwise convolution of ``z`` [B, L, D] by ``w`` [D, K]."""
    K = w.shape[-1]
    return sum(w[:, j] * _shift(z, K - 1 - j) for j in range(K))


@jax.custom_vjp
def gated_short_conv(bcx, w):
    """``bcx`` [B, L, 3D] in the compute dtype, ``w`` [D, K] f32 taps; returns
    ``[B, L, D]`` in ``bcx``'s dtype."""
    with jax.named_scope("short_conv"):
        gate_b, gate_c, x = _thirds(bcx)
        return (gate_c * _taps(gate_b * x, w)).astype(bcx.dtype)


def _fwd(bcx, w):
    return gated_short_conv(bcx, w), (bcx, w)


def _bwd(residuals, g):
    bcx, w = residuals
    K = w.shape[-1]
    with jax.named_scope("short_conv"):
        gate_b, gate_c, x = _thirds(bcx)
        g = g.astype(jnp.float32)
        z = gate_b * x
        d_c = g * gate_c
        # c[t] holds w[:, j] z[t - (K-1) + j]: z[s] reaches c[s + (K-1) - j]
        d_z = sum(w[:, j] * _advance(d_c, K - 1 - j) for j in range(K))
        d_w = jnp.stack(
            [jnp.sum(d_c * _shift(z, K - 1 - j), axis=(0, 1))
             for j in range(K)], axis=-1)
        d_bcx = jnp.concatenate(
            [d_z * x, g * _taps(z, w), d_z * gate_b], axis=-1)
        return d_bcx.astype(bcx.dtype), d_w.astype(w.dtype)


gated_short_conv.defvjp(_fwd, _bwd)


@jax.checkpoint
def causal_conv_silu(x, w):
    """``silu(conv(x))`` of ``x`` [B, L, D] in the compute dtype by the f32
    taps ``w`` [D, K] (the same causal depthwise convolution, any ``K``), in
    f32 and as f32; the backward pass keeps ``x`` and ``w`` only."""
    return jax.nn.silu(_taps(x.astype(jnp.float32), w))

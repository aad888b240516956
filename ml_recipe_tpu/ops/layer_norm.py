"""Fused LayerNorm Pallas TPU kernel (forward + one-pass backward).

Attacks the HBM-bound elementwise segment of the bert-base step
(artifacts/r4/elementwise_floor.json, an old capture): XLA differentiates ``nn.LayerNorm`` into a
row-wise dx loop PLUS separate column reductions for dgamma/dbeta over the
[B*L, C] arrays, re-reading g and the saved input for each — ~5 full
activation sweeps of HBM traffic. The fused backward here does ONE pass:
each grid step reads its [rows, C] block of g and h once, writes dx, and
accumulates dgamma/dbeta partials into a revisited [1, C] f32 output block
that stays VMEM-resident across the sequential TPU grid (same idiom as the
q-blocked attention backward's dk/dv accumulation) — ~3 sweeps total.

Statistics are recomputed in the backward from the saved input (f32 mean /
rsqrt over C is VPU work on data the kernel already holds; saving forward
mean/rstd would add an [N, 1] lane-padded residual stream for no HBM win).

The reference runs LayerNorm inside HF BertModel's CUDA kernels
(SURVEY.md §2.2 "HF BERT CUDA kernels"); this is the TPU-native replacement
for its fused LN, not a translation.

The op ships OFF by default (``ln_impl='xla'``): the one on-chip A/B on
record measured it a wash (artifacts/r4/bench_seq512_lnfused.json vs
bench_seq512.json); ROADMAP D3 has its removal.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import aot
from .flash_attention import _VMEM_BUDGET


def _xla_layer_norm(h, gamma, beta, eps, dtype):
    """Plain XLA path, flax-equivalent numerics: stats in f32, affine in the
    compute dtype (mirrors nn.LayerNorm's upcast-for-stats behavior)."""
    hf = h.astype(jnp.float32)
    mu = jnp.mean(hf, axis=-1, keepdims=True)
    xc = hf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(dtype)


def _rows_block(N: int, C: int, itemsize: int):
    """Rows per grid step, or ``None`` when no [blk, C] geometry fits VMEM.

    Sized for the BACKWARD (the heavier direction): h and g in-blocks plus
    the dh out-block, all double-buffered at the activation itemsize, next
    to ~6 [blk, C] f32 temporaries (h/g upcasts, xhat, g*gamma, dh). The
    forward reuses the same block size — strictly lighter, so a fit here
    fits there. blk must divide N exactly (pallas grids don't pad) and be a
    sublane multiple (8)."""
    per_row = C * (3 * 2 * itemsize + 6 * 4)
    best = None
    for blk in range(8, min(N, 1024) + 1, 8):
        if N % blk == 0 and per_row * blk <= _VMEM_BUDGET:
            best = blk
    return best


def _fused_geometry(N: int, C: int, itemsize: int):
    """The row block for a REAL-hardware fused execution, or ``None`` when
    none is legal: lane-tiled feature dim (C % 128) and a VMEM-feasible row
    block. The single feasibility rule consulted by both the 'auto' gate
    and the explicit 'fused' dispatch (they must not be able to disagree);
    interpret-mode tests may call the op below this gate."""
    if C % 128 != 0:
        return None
    return _rows_block(N, C, itemsize)


def supports_fused_ln(N: int, C: int, itemsize: int) -> bool:
    return _fused_geometry(N, C, itemsize) is not None


def _ln_fwd_kernel(h_ref, gamma_ref, beta_ref, y_ref, *, eps):
    h = h_ref[...].astype(jnp.float32)                      # [blk, C]
    mu = jnp.mean(h, axis=1, keepdims=True)
    xc = h - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * gamma_ref[...].astype(jnp.float32) + beta_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(h_ref, gamma_ref, g_ref, dh_ref, dg_ref, db_ref, *, eps):
    i = pl.program_id(0)
    h = h_ref[...].astype(jnp.float32)                      # [blk, C]
    g = g_ref[...].astype(jnp.float32)
    gamma = gamma_ref[...].astype(jnp.float32)              # [1, C]

    mu = jnp.mean(h, axis=1, keepdims=True)
    xc = h - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd

    gg = g * gamma
    m1 = jnp.mean(gg, axis=1, keepdims=True)
    m2 = jnp.mean(gg * xhat, axis=1, keepdims=True)
    dh_ref[...] = ((gg - m1 - xhat * m2) * rstd).astype(dh_ref.dtype)

    # dgamma/dbeta partials accumulate in the revisited [1, C] f32 output
    # block — resident in VMEM across the sequential grid, written to HBM
    # once at the end (this is the pass XLA spends two extra activation
    # sweeps on)
    pg = jnp.sum(g * xhat, axis=0, keepdims=True)
    pb = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dg_ref[...] = pg
        db_ref[...] = pb

    @pl.when(i > 0)
    def _():
        dg_ref[...] += pg
        db_ref[...] += pb


def _build_ln_fwd_call(N, C, blk, eps, in_dtype, out_dtype, interpret):
    """The forward ``pallas_call`` for one geometry, shared by the real
    execution path and the compile probe so they cannot drift (same
    discipline as the attention ``_build_fused_bwd_call``). Takes
    ``(h [N, C], gamma [1, C], beta [1, C])``."""
    del in_dtype  # the argument arrays carry it; kept for probe symmetry
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec((blk, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, C), out_dtype),
        interpret=interpret,
    )


def _build_ln_bwd_call(N, C, blk, eps, in_dtype, interpret):
    """The backward ``pallas_call`` for one geometry (probe-shared). Takes
    ``(h [N, C], gamma [1, C], g [N, C])`` and returns (dh, dgamma, dbeta)."""
    return pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec((blk, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((blk, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C), in_dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        interpret=interpret,
    )


_ln_probe_results: dict = {}


def _fused_ln_compiles(blk, C, in_dtype, out_dtype, gamma_dtype, beta_dtype,
                       eps) -> bool:
    """Cached Mosaic compile probe for BOTH kernel directions at one block
    geometry (N = 2*blk: two grid steps — a one-step grid gets no second
    pipeline buffer and under-reports scoped VMEM, see flash_attention's
    ``_PROBE_BATCH``; from two steps on the verdict covers every N sharing
    the block). The LN kernel has no
    tunable knob to walk down, so a rejection routes the caller to the XLA
    path instead of crashing the training step at trace time; this is the
    safety net that makes ``--ln_impl fused`` runnable on a chip generation
    the kernel has never met (the attention kernels' probe discipline).

    ``gamma_dtype``/``beta_dtype`` are the affine params' dtypes — probed
    (and keyed) INDIVIDUALLY at their real values so no argument can pass
    the probe with one dtype and execute with another."""
    key = (blk, C, str(in_dtype), str(out_dtype), str(gamma_dtype),
           str(beta_dtype))
    ok = _ln_probe_results.get(key)
    if ok is None:
        h_s = jax.ShapeDtypeStruct((2 * blk, C), in_dtype)
        gamma_s = jax.ShapeDtypeStruct((1, C), gamma_dtype)
        beta_s = jax.ShapeDtypeStruct((1, C), beta_dtype)
        g_s = jax.ShapeDtypeStruct((2 * blk, C), out_dtype)
        try:
            # validation compiles ride the AOT program store: the verdict
            # memo above is per-process, but the compiled probes persist —
            # a warm restart re-validates by LOADING, not re-compiling
            fwd = _build_ln_fwd_call(2 * blk, C, blk, eps, in_dtype,
                                     out_dtype, interpret=False)
            aot.probe_compile("ln-probe-fwd", fwd, h_s, gamma_s, beta_s,
                              geometry=f"{blk}x{C}")
            bwd = _build_ln_bwd_call(2 * blk, C, blk, eps, in_dtype,
                                     interpret=False)
            aot.probe_compile("ln-probe-bwd", bwd, h_s, gamma_s, g_s,
                              geometry=f"{blk}x{C}")
            ok = True
        except Exception as e:  # noqa: BLE001 - any rejection means fallback
            logging.getLogger(__name__).warning(
                "fused layer_norm kernel did not compile at blk=%d, C=%d "
                "(%s -> %s); using the XLA path. Error: %s",
                blk, C, in_dtype, out_dtype, e,
            )
            ok = False
        _ln_probe_results[key] = ok
    return ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ln_flat(h, gamma, beta, eps, out_dtype, interpret):
    y, _ = _fused_ln_flat_fwd(h, gamma, beta, eps, out_dtype, interpret)
    return y


def _fused_ln_flat_fwd(h, gamma, beta, eps, out_dtype, interpret):
    N, C = h.shape
    blk = _rows_block(N, C, h.dtype.itemsize)
    assert blk is not None, (N, C)  # dispatcher gates on supports_fused_ln
    y = _build_ln_fwd_call(N, C, blk, eps, h.dtype, out_dtype, interpret)(
        h, gamma[None, :], beta[None, :]
    )
    return y, (h, gamma)


def _fused_ln_flat_bwd(eps, out_dtype, interpret, res, g):
    h, gamma = res
    N, C = h.shape
    blk = _rows_block(N, C, h.dtype.itemsize)
    dh, dg, db = _build_ln_bwd_call(N, C, blk, eps, h.dtype, interpret)(
        h, gamma[None, :], g
    )
    return dh, dg[0].astype(gamma.dtype), db[0].astype(gamma.dtype)


_fused_ln_flat.defvjp(_fused_ln_flat_fwd, _fused_ln_flat_bwd)


def layer_norm(h, gamma, beta, *, eps: float = 1e-12, dtype=jnp.float32,
               impl: str = "auto"):
    """LayerNorm over the trailing axis of ``h`` ([..., C]) with f32 stats.

    ``impl``:
    - 'xla': plain path, any backend;
    - 'fused': Pallas kernel on TPU; off-TPU falls back to XLA (pallas
      interpret mode is a correctness vehicle, ~1000x too slow to be a
      runtime path — a CPU debug run with a TPU config must not crawl);
    - 'interpret': the kernel under pallas interpret mode on any backend
      (tests drive the real kernel path on the CPU mesh with this);
    - 'auto': fused on TPU when the geometry qualifies, else xla."""
    C = h.shape[-1]
    N = h.size // C
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = (
            "fused"
            if on_tpu and supports_fused_ln(N, C, h.dtype.itemsize)
            else "xla"
        )
    if impl == "fused" and not on_tpu:
        logging.getLogger(__name__).info(
            "ln_impl='fused' on a %s backend: using the XLA path "
            "(interpret mode is for tests — pass impl='interpret' to force "
            "the kernel).", jax.default_backend(),
        )
        impl = "xla"
    if impl in ("fused", "interpret"):
        # 'fused' (real hardware) requires the lane-tiled geometry rule of
        # _fused_geometry and a passing Mosaic compile probe — a rejected
        # geometry must fall back, not crash the training step at trace
        # time; 'interpret' needs only a row block
        blk = (
            _fused_geometry(N, C, h.dtype.itemsize)
            if impl == "fused"
            else _rows_block(N, C, h.dtype.itemsize)
        )
        if blk is None:
            logging.getLogger(__name__).warning(
                "fused layer_norm has no feasible kernel geometry for "
                "N=%d, C=%d; using the XLA path instead.", N, C,
            )
        elif impl == "fused" and not _fused_ln_compiles(
            blk, C, h.dtype, jnp.dtype(dtype), gamma.dtype, beta.dtype,
            float(eps)
        ):
            pass  # the probe already warned with the compile error
        else:
            y = _fused_ln_flat(
                h.reshape(N, C), gamma, beta, float(eps),
                jnp.dtype(dtype), impl == "interpret",
            )
            return y.reshape(h.shape)
    return _xla_layer_norm(h, gamma, beta, eps, dtype)

"""Attention ops.

The reference's attention lives inside HF BertModel CUDA kernels (SURVEY.md
§2.2). Here it is a first-party op with interchangeable implementations:

- ``xla``: plain einsum softmax attention — XLA fuses it well and it runs on
  any backend (used in tests on the CPU mesh).
- ``pallas``: the TPU kernel regimes — fully-fused (L <= 512), q-blocked
  resident-KV (to ~2k, ``ops.flash_attention``), and streaming-KV
  FlashAttention-2 beyond that (``ops.flash_streaming``, no single-chip
  length ceiling). None materialises the [B,H,L,L] score matrix in HBM,
  and all draw dropout from one absolute-index hash, so regimes are
  interchangeable without changing the noise stream.
- ``ring``: sequence-parallel ring attention over the mesh ``seq`` axis
  (multi-chip long context).

``dot_product_attention`` picks per the ``impl`` argument. ``'auto'`` = the
best-qualifying pallas regime on TPU; off-TPU it is xla, and on a TPU a shape
no regime can serve takes xla WITH a warning. ``'pallas'`` is a demand: a
shape no regime can serve raises instead of quietly running something else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _xla_attention(
    q: jnp.ndarray,  # [B, L, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],  # [B, L] 1=real, 0=pad
    *,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    dtype=jnp.float32,
    segment_ids: Optional[jnp.ndarray] = None,  # [B, L] 0=pad, 1..S packed
    batch_shard=None,  # (index, count): q holds that shard's rows of the batch
    causal: bool = False,
    window: Optional[int] = None,  # with causal: keys j with i - j < window
) -> jnp.ndarray:
    depth = q.shape[-1]
    scale = 1.0 / jnp.sqrt(depth).astype(dtype)
    if causal:      # 1/sqrt(192) is no bf16 number: keep the scale in f32
        scale = jnp.float32(1.0 / depth ** 0.5)

    # [B, H, Lq, Lk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale

    big_neg = jnp.finfo(jnp.float32).min
    if segment_ids is not None:
        # block-diagonal attention for packed sequences: a query attends
        # only keys of its OWN segment (and pad keys — seg 0 — never attend
        # or get attended: seg 0 rows produce garbage that downstream
        # masking ignores, the same contract as pad rows today)
        allowed = (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        ) & (segment_ids[:, None, None, :] > 0)
        scores = jnp.where(allowed, scores, big_neg)
    elif mask is not None:
        scores = jnp.where(mask[:, None, None, :] > 0, scores, big_neg)
    if causal:
        L = scores.shape[-1]
        allowed = jnp.tril(jnp.ones((L, L), bool))
        if window is not None:      # the band: itself and window - 1 before
            allowed = allowed & ~jnp.tril(jnp.ones((L, L), bool), -window)
        scores = jnp.where(allowed, scores, big_neg)

    # softmax in f32 for numerical stability regardless of compute dtype
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)

    if dropout_rate > 0.0 and dropout_rng is not None:
        if batch_shard is None:
            keep = jax.random.bernoulli(
                dropout_rng, 1.0 - dropout_rate, probs.shape)
        else:
            # this shard's rows of the WHOLE batch's draw, so the mask stays
            # the unsharded call's (as it is under GSPMD, which replicates
            # the generator); the price is the whole draw on every shard
            index, count = batch_shard
            rows = probs.shape[0]
            keep = jax.lax.dynamic_slice_in_dim(
                jax.random.bernoulli(
                    dropout_rng, 1.0 - dropout_rate,
                    (count * rows,) + probs.shape[1:]),
                index * rows, rows)
        probs = probs * keep.astype(dtype) / (1.0 - dropout_rate)

    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _dropout_seed(dropout_rng):
    """int32 seed array (1,) for the in-kernel/in-flight dropout hash — ONE
    derivation shared by the pallas and ring paths so their documented
    mask-identity cannot drift."""
    assert dropout_rng is not None, "dropout_rate > 0 needs dropout_rng"
    return jax.random.randint(
        dropout_rng, (1,), minval=jnp.iinfo(jnp.int32).min,
        maxval=jnp.iinfo(jnp.int32).max, dtype=jnp.int32,
    )


def _kernel_shard_axes(mesh):
    """``(batch_axis, head_axis)`` a Pallas attention call must be
    ``shard_map``-ped over on ``mesh`` (either may be None), or None when one
    device runs it whole. GSPMD cannot partition a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned"): under ``--mesh data:N``
    the batch dimension arrives sharded over ``data``, under tensor
    parallelism the head dimension over ``model``, and the kernel has to be
    told. ``seq`` is ring attention's and ``pipe`` runs inside the pipeline
    island's own shard_map; a mesh that spans either is left alone."""
    from ..parallel.sharding import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS

    if mesh is None or mesh.devices.size == 1:
        return None
    size = dict(zip(mesh.axis_names, mesh.devices.shape))
    if size.get(SEQ_AXIS, 1) > 1 or size.get(PIPE_AXIS, 1) > 1:
        return None
    batch_axis = DATA_AXIS if size.get(DATA_AXIS, 1) > 1 else None
    head_axis = MODEL_AXIS if size.get(MODEL_AXIS, 1) > 1 else None
    if batch_axis is None and head_axis is None:
        return None
    return batch_axis, head_axis


def _manual_batch_axis(mesh):
    """The batch axis of ``mesh`` where an enclosing ``shard_map`` already
    made it manual (a data-parallel island: the caller then holds one
    shard's rows whole and nothing here may shard them again), else None."""
    axes = _kernel_shard_axes(mesh)
    if axes is None or (
            axes[0] not in jax.sharding.get_abstract_mesh().manual_axes):
        return None
    assert axes[1] is None, "a data island runs on a data-only mesh"
    return axes[0]


def sharded_kernel_call(kernel, mesh, axes, q, k, v, mask, seed):
    """Run ``kernel(q, k, v, mask, seed)`` (a Pallas attention regime) once
    per shard of the batch and head dimensions.

    The dropout masks stay those of the unsharded call: the kernels key
    their hash by ``seed_row + head * PRIME`` (``flash_attention._row_seeds``),
    so the GLOBAL per-row seed vector is built here, sharded with the batch,
    and each head shard folds its first head's offset in."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.compat import shard_map
    from .flash_attention import _row_seeds

    batch_axis, head_axis = axes
    B, L, H, D = q.shape
    if mask is None:
        mask = jnp.ones((B, L), dtype=jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), dtype=jnp.int32)
    rows = _row_seeds(seed, B, H)  # [B] global seeds (B == 1: shape (1,))
    heads_per_shard = H // (mesh.shape[head_axis] if head_axis else 1)

    def per_shard(q, k, v, mask, rows):
        if head_axis is not None:
            first_head = jax.lax.axis_index(head_axis) * heads_per_shard
            rows = rows + first_head.astype(jnp.int32) * jnp.int32(-1640531527)
        return kernel(q, k, v, mask, rows)

    qkv = P(batch_axis, None, head_axis, None)
    return shard_map(
        per_shard, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(batch_axis, None), P(batch_axis)),
        out_specs=qkv, check_vma=False,
    )(q, k, v, mask, rows)


@functools.lru_cache(maxsize=None)
def _warn_auto_takes_xla(L: int, H: int, D: int, rate: float) -> None:
    """Once per shape (every layer traces the same one)."""
    import logging

    logging.getLogger(__name__).warning(
        f"attention 'auto': no Pallas kernel regime can run L={L}, "
        f"per-shard heads {H}, D={D}, rate={rate} on this TPU and mesh; "
        f"running XLA attention for this shape."
    )


def _causal_attention(q, k, v, mask, *, dropout_rate, dtype, impl, mesh,
                      segment_ids, causal, window=None):
    """The causal two-width family: ``ops/flash_causal.py`` (under a sliding
    window shorter than the rows ``ops/flash_window.py``) on a TPU where its
    shapes hold, XLA otherwise. What the family lacks raises by name."""
    from .flash_causal import causal_attention, supports_causal

    if window is not None and window >= q.shape[1]:
        window = None       # a window as long as the rows is the causal mask
    lacking = [what for what, asked in (
        ("a full (non-causal) mask with d_qk != d_v", not causal),
        ("attention dropout", dropout_rate > 0.0),
        ("sequence packing (segment ids)", segment_ids is not None),
        ("ring attention over a seq axis", impl == "ring"),
    ) if asked]
    if lacking:
        raise NotImplementedError(
            f"causal / two-width attention (q{tuple(q.shape)}, "
            f"v{tuple(v.shape)}) does not support {', '.join(lacking)}")
    L = q.shape[1]
    axes = _kernel_shard_axes(mesh)
    if _manual_batch_axis(mesh) is not None:
        axes = None      # a data island: this shard's rows arrive whole
    divides = axes is None or (
        q.shape[0] % (mesh.shape[axes[0]] if axes[0] else 1) == 0
        and k.shape[2] % (mesh.shape[axes[1]] if axes[1] else 1) == 0)
    shapes_ok = divides and supports_causal(L, q.shape[-1], v.shape[-1])
    if window is not None:
        from .flash_window import supports_window, window_attention

        shapes_ok = divides and supports_window(
            L, q.shape[-1], v.shape[-1], window)
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" and shapes_ok
                else "xla")
    if impl == "xla":
        group = q.shape[2] // k.shape[2]
        if group > 1:       # grouped-query heads: head h reads k/v h // group
            k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return _xla_attention(q, k, v, mask, dtype=dtype, causal=True,
                              window=window)
    if not shapes_ok:
        raise ValueError(
            f"attention impl 'pallas' was demanded but the causal kernels "
            f"cannot run q{tuple(q.shape)}, v{tuple(v.shape)} on this mesh")

    def kernel(q, k, v, mask, _seed):
        if window is not None:
            return window_attention(q, k, v, mask, window=window, dtype=dtype)
        return causal_attention(q, k, v, mask, dtype=dtype)

    if axes is None:
        return kernel(q, k, v, mask, None)
    return sharded_kernel_call(kernel, mesh, axes, q, k, v, mask, None)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    dtype=jnp.float32,
    impl: str = "auto",
    mesh=None,
    segment_ids: Optional[jnp.ndarray] = None,
    causal: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Multi-head attention over [B, L, H, D] tensors with a [B, L] key mask.

    ``causal`` adds the lower-triangular mask; q and k may then be wider or
    narrower than v (a latent attention block's training form), and k and v
    may have fewer heads than q (grouped-query heads: ``[B, L, H_kv, D]``,
    query head ``h`` reads ``h // (H / H_kv)``). Either takes the causal
    two-width family (``_causal_attention``), chosen from the shapes alone.
    ``window`` (with ``causal``) narrows the triangle to the band ``0 <= i -
    j < window``: a query sees itself and the ``window - 1`` keys before it.

    ``impl='ring'`` runs sequence-parallel ring attention over the mesh
    ``seq`` axis (requires ``mesh``; composes with the ``data`` axis).

    ``segment_ids`` ([B, L] int32, 0 = pad, 1..S = packed segment) switches
    every implementation to the BLOCK-DIAGONAL mask of sequence packing:
    query i attends key j iff ``seg[i] == seg[j] != 0``. The ids array
    subsumes the key-validity mask (``seg > 0``), so ``mask`` is ignored
    when it is given. Under ``impl='ring'`` segment ids need the composed
    streaming-ring inner (a legal streaming geometry at the local shard
    length); ring_attention raises otherwise.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and at least one key a query")
    if causal or q.shape[-1] != v.shape[-1]:
        return _causal_attention(
            q, k, v, mask, dropout_rate=dropout_rate, dtype=dtype, impl=impl,
            mesh=mesh, segment_ids=segment_ids, causal=causal, window=window)

    if impl == "ring":
        from ..parallel.sharding import DATA_AXIS, SEQ_AXIS
        from .ring_attention import ring_attention

        assert mesh is not None, "impl='ring' requires a mesh"
        assert SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1, (
            f"impl='ring' needs a '{SEQ_AXIS}' mesh axis > 1 "
            f"(--mesh 'data:N,seq:M'); got {dict(zip(mesh.axis_names, mesh.devices.shape))}"
        )
        batch_axis = (
            DATA_AXIS
            if DATA_AXIS in mesh.axis_names and mesh.shape[DATA_AXIS] > 1
            else None
        )
        seed = _dropout_seed(dropout_rng) if dropout_rate > 0.0 else None
        # segment_ids route through the composed streaming-ring inner
        # (ring_attention raises when no legal geometry exists at the
        # local length — the dense inner is unsegmented)
        return ring_attention(
            q, k, v, mask, mesh=mesh, axis_name=SEQ_AXIS,
            batch_axis=batch_axis, dtype=dtype,
            rate=dropout_rate, seed=seed, segment_ids=segment_ids,
        )

    manual_axis = _manual_batch_axis(mesh)
    if impl in ("auto", "pallas"):
        from .flash_attention import (
            supports_blocked_bwd, supports_blocked_fwd, supports_fused_bwd,
        )
        from .flash_streaming import supports_streaming

        L, H, D = q.shape[1], q.shape[2], q.shape[3]
        # on a multi-device mesh each shard runs the kernel on its own
        # heads, so feasibility is the shard's (H below is per shard)
        axes = _kernel_shard_axes(mesh)
        # already manual over the batch axis: this shard's rows arrive whole,
        # the kernel is called directly (as under ``pipe``) and only the
        # dropout seeds need the rows' global index
        if manual_axis is not None:
            axes = None
        divides = True
        if axes is not None:
            rows_per = mesh.shape[axes[0]] if axes[0] else 1
            heads_per = mesh.shape[axes[1]] if axes[1] else 1
            divides = q.shape[0] % rows_per == 0 and H % heads_per == 0
            H = H // heads_per if divides else H
        in_isz = jnp.dtype(q.dtype).itemsize
        out_isz = jnp.dtype(dtype).itemsize
        # The real input/output/mask dtypes ride along so the feasibility
        # answer comes from the SAME autotune key the execution path will
        # select through (compile-probe-validated on TPU, analytic
        # arithmetic elsewhere) — a differently-keyed answer could disagree
        # with the execution selection and double-probe.
        # Dropout needs BOTH kernel directions feasible: the forward's
        # in-kernel mask cannot be reproduced by an XLA fallback backward.
        # Sequence packing reuses the mask operand as the segment-id plane
        # (0 = pad), so the kernel mask is segment_ids when packing is on.
        segmented = segment_ids is not None
        kernel_mask = segment_ids if segmented else mask
        mask_dtype = kernel_mask.dtype if kernel_mask is not None else jnp.int32
        blocked_ok = supports_blocked_fwd(
            L, H, D, in_isz, out_isz, dropout_rate,
            in_dtype=q.dtype, out_dtype=dtype, mask_dtype=mask_dtype,
            segmented=segmented,
        ) and (
            dropout_rate == 0.0
            or supports_blocked_bwd(L, H, D, in_isz, dropout_rate,
                                    out_itemsize=out_isz,
                                    in_dtype=q.dtype, out_dtype=dtype,
                                    mask_dtype=mask_dtype,
                                    segmented=segmented)
        )
        resident_ok = supports_fused_bwd(L) or blocked_ok
        # The streaming-KV regime serves lengths the resident-KV kernels
        # decline (~>2k). The proven regimes keep priority where they
        # apply — their on-chip numbers are recorded; streaming replaces
        # only the XLA fallback.
        streaming_ok = not resident_ok and supports_streaming(
            L, H, D, in_isz, out_isz, dropout_rate,
            in_dtype=q.dtype, out_dtype=dtype, mask_dtype=mask_dtype,
            segmented=segmented,
        )
        # a batch or head count the mesh does not divide cannot be
        # shard_map-ped, and a Mosaic kernel is never partitioned for us
        shapes_ok = divides and (resident_ok or streaming_ok)

    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and not shapes_ok:
            _warn_auto_takes_xla(L, H, D, float(dropout_rate))
        impl = "pallas" if on_tpu and shapes_ok else "xla"

    if impl == "pallas":
        if not shapes_ok:
            raise ValueError(
                f"attention impl 'pallas' was demanded but no kernel regime "
                f"can run q{tuple(q.shape)} (per-shard heads {H}, "
                f"rate={dropout_rate}) on this mesh; use impl='auto' or "
                f"'xla' for this shape."
            )
        seed = _dropout_seed(dropout_rng) if dropout_rate > 0.0 else None
        if streaming_ok:
            from .flash_streaming import streaming_attention as regime
        else:
            from .flash_attention import flash_attention as regime

        def kernel(q, k, v, kernel_mask, seed):
            return regime(
                q, k, v, kernel_mask, seed=seed, dtype=dtype,
                rate=dropout_rate, segmented=segmented,
            )

        if axes is None:
            if manual_axis is not None and seed is not None:
                from .flash_attention import _row_seeds

                rows = q.shape[0]
                seed = _row_seeds(
                    seed, rows, H,
                    first_row=jax.lax.axis_index(manual_axis) * rows)
            return kernel(q, k, v, kernel_mask, seed)
        return sharded_kernel_call(
            kernel, mesh, axes, q, k, v, kernel_mask, seed)

    batch_shard = None
    if manual_axis is not None:
        batch_shard = (
            jax.lax.axis_index(manual_axis), mesh.shape[manual_axis])
    return _xla_attention(
        q, k, v, mask, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        dtype=dtype, segment_ids=segment_ids, batch_shard=batch_shard,
    )

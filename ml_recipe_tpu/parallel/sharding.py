"""Sharding rules and helpers.

Replaces the reference's replication-everywhere model (full replica params +
DistributedSampler data split, trainer.py:150-166) with explicit
`NamedSharding` layouts over the mesh:

- batches: leading (batch) dim over ``data``; optional sequence dim over
  ``seq`` for context parallelism;
- params: replicated by default; under tensor parallelism (``model`` axis)
  attention QKV / MLP kernels are sharded on the width dimension and the
  following projections on the input dimension, so each matmul stays local
  and XLA inserts the single reduce per block GSPMD-style.

``make_global_array`` assembles per-host numpy shards into one global
``jax.Array`` (the multi-host replacement for DistributedSampler: each host
feeds only its slice, SURVEY.md §7).
"""

from __future__ import annotations

import logging
import re
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


# Tensor-parallel partition rules: (param-path regex -> PartitionSpec).
# Kernel shapes are [in, out]; embeddings [vocab, hidden].
TP_RULES = [
    (r".*attention/(query|key|value)/kernel$", P(None, MODEL_AXIS)),
    (r".*attention/(query|key|value)/bias$", P(MODEL_AXIS)),
    (r".*attention/output/kernel$", P(MODEL_AXIS, None)),
    (r".*mlp/intermediate/kernel$", P(None, MODEL_AXIS)),
    (r".*mlp/intermediate/bias$", P(MODEL_AXIS)),
    (r".*mlp/output/kernel$", P(MODEL_AXIS, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(params, mesh: Mesh) -> dict:
    """PartitionSpec tree for a param tree: TP rules when the mesh has a
    ``model`` axis (>1), replicated otherwise."""
    has_tp = MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1

    def spec_for(path, leaf):
        if has_tp:
            path_s = _path_str(path)
            for pattern, spec in TP_RULES:
                if re.match(pattern, path_s):
                    return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


class ZeroLeafPlan(NamedTuple):
    """Per-leaf ZeRO-1 placement: ``spec`` is the PartitionSpec of the
    (possibly padded) stored leaf; ``axis``/``padded`` name the dim carrying
    the ``data`` axis and its padded extent (``axis is None`` = replicated,
    ``padded == shape[axis]`` = no padding was needed)."""

    spec: P
    axis: Optional[int]
    padded: Optional[int]


# Param paths eligible for stage-local ``pipe``-axis sharding: exactly the
# leaves a pipeline stage consumes exclusively (the embedding table feeds
# only rank 0's refill; each encoder layer runs on exactly one stage).
# Pooler/head leaves run outside (or on the last tick of) the island on
# every rank's collected outputs, so they stay replicated — they are a
# rounding error of bert-large's bytes next to the layer stack.
STAGE_SCOPE_RE = re.compile(r"(^|/)transformer/(embeddings|layer_\d+)(/|$)")


def _zero_leaf_plan(path, shape, *, data_size: int,
                    has_tp: bool, min_size,
                    pipe_size: int = 1) -> ZeroLeafPlan:
    """The ONE dim chooser every ZeRO-1 consumer shares (state shardings,
    gradient constraints, byte modeling, checkpoint reconciliation):
    tensor-parallel axes are honored first; with ``pipe_size > 1`` the
    ``pipe`` axis then claims the largest stage-scope dim divisible by the
    stage count (stage-local param/optimizer storage — no padding: encoder
    dims are powers of two in practice, and a leaf with no dividing dim
    simply stays pipe-replicated); the ``data`` axis finally lands on
    the largest remaining dim already divisible by the axis size — or, when
    none divides, on the largest remaining dim PADDED up to the next
    multiple (this JAX rejects uneven shardings, so divisibility is bought
    with explicit zero padding of the stored state). Leaves below
    ``min_size`` elements (and scalars) stay replicated: sharding them buys
    nothing and costs collective latency."""
    axes = [None] * len(shape)
    path_s = _path_str(path)
    if has_tp:
        for pattern, spec in TP_RULES:
            if re.match(pattern, path_s):
                axes = list(spec) + [None] * (len(shape) - len(spec))
                break
    if pipe_size > 1 and STAGE_SCOPE_RE.search(path_s):
        pipe_free = [
            (dim, i) for i, dim in enumerate(shape)
            if axes[i] is None and dim % pipe_size == 0
        ]
        if pipe_free:
            _, i = max(pipe_free)
            axes[i] = PIPE_AXIS
    if data_size <= 1 or int(np.prod(shape or (0,))) < min_size:
        return ZeroLeafPlan(P(*axes), None, None)
    free = [(dim, i) for i, dim in enumerate(shape) if axes[i] is None]
    divisible = [(dim, i) for dim, i in free if dim % data_size == 0]
    if divisible:
        dim, i = max(divisible)
        padded = dim
    elif free and max(free)[0] >= 2:
        dim, i = max(free)
        padded = -(-dim // data_size) * data_size  # ceil to a multiple
    else:
        return ZeroLeafPlan(P(*axes), None, None)
    axes[i] = DATA_AXIS
    return ZeroLeafPlan(P(*axes), i, padded)


def zero1_plan(tree, mesh: Mesh, *, min_size: int = 16384,
               stage_pipe: bool = False):
    """ZeRO-1 placement plan for a (shape-carrying) pytree: one
    :class:`ZeroLeafPlan` per leaf. Works on live arrays and on
    ``jax.eval_shape`` outputs alike — only ``.shape`` is read. Leaf paths
    inside optax states end with the param path (e.g.
    ``.../mu/encoder/layer_0/attention/query/kernel``), so the tensor-
    parallel rules apply unchanged. With ``stage_pipe`` the ``pipe`` axis
    claims its stage-scope dim first, so the data-axis padded-leaf plan
    runs WITHIN a stage's leaf set (ZeRO-1 under pipeline)."""
    data_size = int(mesh.shape.get(DATA_AXIS, 1))
    has_tp = MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1
    pipe_size = (
        int(mesh.shape.get(PIPE_AXIS, 1)) if stage_pipe else 1
    )

    def plan_for(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        return _zero_leaf_plan(
            path, shape, data_size=data_size, has_tp=has_tp,
            min_size=min_size, pipe_size=pipe_size,
        )

    return jax.tree_util.tree_map_with_path(plan_for, tree)


def zero_pspecs(state_shapes, mesh: Mesh, *, min_size: int = 16384,
                stage_pipe: bool = False):
    """ZeRO-1 PartitionSpec tree for an optimizer-state (shape) tree.

    The reference replicates optimizer state on every replica (SURVEY.md
    §2.3 'full replica optimizer state'); here each moment tensor is sharded
    over the ``data`` axis so its memory scales 1/N with data parallelism —
    XLA all-gathers the (sharded) param updates it produces, which is the
    ZeRO-1 communication pattern. The specs assume the leaves are already at
    their PADDED extents (``zero_pad_tree``) where the plan demands padding.
    """
    return jax.tree_util.tree_map(
        lambda z: z.spec,
        zero1_plan(state_shapes, mesh, min_size=min_size,
                   stage_pipe=stage_pipe),
        is_leaf=lambda x: isinstance(x, ZeroLeafPlan),
    )


def zero_pad_tree(tree, plan):
    """Zero-pad each leaf along its plan axis up to the padded extent (the
    divisibility the ``data``-axis sharding needs). No-op leaves (plan axis
    None, or already divisible) pass through untouched — jnp.pad with a
    zero width is the identity, so the padded update step costs nothing on
    the (typical) leaves whose dims already divide."""

    def pad(x, z):
        if z.axis is None or z.padded == x.shape[z.axis]:
            return x
        widths = [(0, 0)] * x.ndim
        widths[z.axis] = (0, z.padded - x.shape[z.axis])
        return jnp.pad(x, widths)

    return jax.tree_util.tree_map(
        pad, tree, plan, is_leaf=lambda x: isinstance(x, ZeroLeafPlan)
    )


def zero_unpad_tree(tree, plan, logical):
    """Slice padded leaves back to the logical shapes of ``logical`` (a
    shape-carrying twin tree) — the inverse of :func:`zero_pad_tree`."""

    def unpad(x, z, ref):
        shape = tuple(ref.shape)
        if z.axis is None or tuple(x.shape) == shape:
            return x
        return jax.lax.slice(x, (0,) * x.ndim, shape)

    return jax.tree_util.tree_map(
        unpad, tree, plan, logical,
        is_leaf=lambda x: isinstance(x, ZeroLeafPlan),
    )


def opt_state_bytes_per_chip(opt_state) -> int:
    """MEASURED per-device resident bytes of a live optimizer-state tree:
    each leaf contributes one shard's bytes (its sharding's per-device
    shard shape), so a ZeRO-sharded state reports ~1/N of its replicated
    footprint. Host (numpy) leaves count in full — they are replicated by
    construction."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        shape = tuple(np.shape(leaf))
        itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                shape = tuple(sharding.shard_shape(shape))
            except Exception as e:  # noqa: BLE001 - exotic sharding
                logger.debug(
                    "shard_shape unavailable for %s (%s); counting the "
                    "full shape", type(sharding).__name__, e,
                )
        total += int(np.prod(shape or (1,), dtype=np.int64)) * itemsize
    return total


def zero1_state_bytes(state_shapes, *, data_size: int,
                      min_size: int = 16384,
                      pipe_size: int = 1) -> dict:
    """MODELED optimizer-state bytes per chip at an arbitrary data-axis
    size — no mesh, no devices, no compile: the HBM-planning probe
    (``bench.py --param_count_probe``) runs this before a TPU window opens.

    Returns ``replicated_bytes`` (every leaf in full — the historical
    layout), ``zero1_bytes`` (each plan-sharded leaf at its padded extent
    divided over ``data_size`` — and, with ``pipe_size > 1``, each
    stage-scope leaf further divided over its ``pipe`` dim — the rest in
    full) and ``sharded_bytes`` (the replicated footprint of exactly the
    leaves the plan shards — the ``(N-1)/N`` savings base the acceptance
    math is stated against).
    """
    data_size = max(1, int(data_size))
    pipe_size = max(1, int(pipe_size))

    def leaf_info(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        z = _zero_leaf_plan(
            path, shape, data_size=data_size, has_tp=False,
            min_size=min_size, pipe_size=pipe_size,
        )
        full = int(np.prod(shape or (1,), dtype=np.int64)) * dtype.itemsize
        shard = list(shape)
        for i, ax in enumerate(z.spec):
            if ax == PIPE_AXIS:
                shard[i] = shard[i] // pipe_size
        if z.axis is None:
            shard_bytes = (
                int(np.prod(shard or [1], dtype=np.int64)) * dtype.itemsize
            )
            sharded = full if shard_bytes < full else 0
            return full, shard_bytes, sharded
        shard[z.axis] = z.padded // data_size
        shard_bytes = int(np.prod(shard, dtype=np.int64)) * dtype.itemsize
        return full, shard_bytes, full

    infos = [
        leaf_info(path, leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state_shapes)[0]
    ]
    return {
        "data_size": data_size,
        "replicated_bytes": sum(i[0] for i in infos),
        "zero1_bytes": sum(i[1] for i in infos),
        "sharded_bytes": sum(i[2] for i in infos),
    }


def leaf_sizes(tree):
    """Per-leaf element counts of ``tree`` in ``tree_leaves`` order — THE
    flattened-gradient layout every bucketed-overlap consumer shares.
    Bucket planning, the static slice offsets, and the train step's flat
    carry all derive from this one function: if they computed sizes
    independently and ever diverged (scalar-leaf handling, say), buckets
    would silently misalign and gradients would unflatten from wrong
    offsets with no error."""
    return [
        int(np.prod(l.shape)) if getattr(l, "ndim", 0) else 1
        for l in jax.tree_util.tree_leaves(tree)
    ]


def zero1_bucket_plan(params, *, bucket_mb: float):
    """Size-targeted gradient buckets over ``params``' flattened leaves
    (``--zero1_overlap bucketed``): each leaf contributes its f32
    ACCUMULATION footprint (gradients accumulate in f32 regardless of the
    param dtype), and contiguous runs close at ``bucket_mb``. The returned
    :class:`~.collectives.GradBucket` ranges index the same
    ``tree_leaves`` order the train step flattens with, so the bucket
    vectors concatenate to the monolithic flat gradient element for
    element."""
    from .collectives import plan_grad_buckets

    return plan_grad_buckets(
        leaf_sizes(params),
        bucket_bytes=max(1, int(float(bucket_mb) * 2**20)), itemsize=4,
    )


def is_single_device(mesh: Mesh) -> bool:
    """True when the mesh is one device — GSPMD placement is skipped entirely
    then: COMMITTED arrays (NamedSharding or explicit device) buy nothing
    without peers. They no longer cost anything either: on one v5e under jax
    0.9.0 the bert-base step (batch 64, split 2) took a median 0.2102 /
    0.2139 s committed against 0.2110 / 0.2100 s uncommitted, same loss (my
    chip run, PR 21) — the bypass is a deletion candidate, ROADMAP D5."""
    return mesh.devices.size == 1


def put_single(x, mesh: Mesh):
    """Single-device placement that avoids committing when possible.

    Uncommitted device_put keeps the fast non-partitioned dispatch path; an
    explicit device target is only used when the mesh is pinned to a device
    other than the process default (where correctness requires commitment).
    """
    device = mesh.devices.flat[0]
    if device == jax.devices()[0]:
        return jax.device_put(x)
    return jax.device_put(x, device)


def shard_params(params, mesh: Mesh, pspecs: Optional[dict] = None):
    """Place a param tree onto the mesh with the given (or derived) specs."""
    if is_single_device(mesh):
        return jax.tree_util.tree_map(lambda x: put_single(x, mesh), params)
    if pspecs is None:
        pspecs = param_pspecs(params, mesh)
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)), params, pspecs
    )


def split_micro(tree, n: int):
    """Host ``[B, ...]`` leaves -> ``[n, B/n, ...]`` (micro-batch major) for
    the in-step gradient-accumulation scan. Shared by the Trainer and the
    device-prefetch placement thread — one definition of the micro layout."""

    def split(x):
        x = np.asarray(x)
        assert x.shape[0] % n == 0, (
            f"local batch {x.shape[0]} not divisible by batch_split {n}"
        )
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])

    return jax.tree_util.tree_map(split, tree)


def batch_pspec(mesh: Mesh, *, shard_seq: bool = False, ndim: int = 2) -> P:
    """Spec for one batch leaf: batch dim over data, optionally seq dim over
    seq for context-parallel runs. Meshes without a data axis (e.g.
    ``pipe:2,model:2``) replicate the batch dim."""
    data_axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    seq_axis = (
        SEQ_AXIS
        if shard_seq and SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1
        else None
    )
    if ndim == 1:
        return P(data_axis)
    return P(data_axis, *([seq_axis] + [None] * (ndim - 2)))


def batch_sharding(mesh: Mesh, batch_tree, *, shard_seq: bool = False):
    """NamedSharding tree matching a (possibly nested) batch structure."""
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, batch_pspec(mesh, shard_seq=shard_seq, ndim=np.ndim(x))),
        batch_tree,
    )


def make_global_array(
    host_batch, mesh: Mesh, *, shard_seq: bool = False, batch_axis: int = 0
):
    """Assemble per-host numpy shards into global jax.Arrays.

    Single-process: a plain sharded device_put. Multi-host: each process
    contributes its local rows (`jax.make_array_from_process_local_data`).
    ``batch_axis`` selects which dim is sharded over ``data`` (axis 1 for
    micro-batch-major [G, B, ...] layouts used by in-step grad accumulation).
    """
    if is_single_device(mesh):
        return jax.tree_util.tree_map(
            lambda x: put_single(np.asarray(x), mesh), host_batch
        )

    def to_global(x):
        x = np.asarray(x)
        if batch_axis == 0:
            spec = batch_pspec(mesh, shard_seq=shard_seq, ndim=x.ndim)
        else:
            axes = [None] * x.ndim
            if DATA_AXIS in mesh.axis_names:
                axes[batch_axis] = DATA_AXIS
            if (shard_seq and x.ndim > batch_axis + 1
                    and SEQ_AXIS in mesh.axis_names
                    and mesh.shape[SEQ_AXIS] > 1):
                # micro-batch-major [G, B, L]: the token dim after the
                # batch dim rides the seq axis, same as batch_pspec
                axes[batch_axis + 1] = SEQ_AXIS
            spec = P(*axes)
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(to_global, host_batch)


def _local_cover_shards(x) -> Optional[dict]:
    """``{bounds: shard}`` for a de-duplicated set of addressable shards that
    covers every element of ``x``, or None when the local shards don't cover
    the array (i.e. some data lives only on other hosts)."""
    total = int(np.prod(x.shape, dtype=np.int64)) if x.shape else 1
    seen: dict = {}
    covered = 0
    for sh in x.addressable_shards:
        bounds = tuple(
            (int(s.start or 0), int(s.stop if s.stop is not None else dim))
            for s, dim in zip(sh.index, x.shape)
        )
        if bounds in seen:
            continue
        seen[bounds] = sh
        vol = int(np.prod([b - a for a, b in bounds], dtype=np.int64)) if bounds else 1
        covered += vol
    if covered != total:
        return None
    # volume-sum coverage is only sound if the de-duplicated bounds are
    # pairwise disjoint; overlapping-but-unequal index ranges would
    # double-count and leave unwritten np.empty garbage downstream. Not
    # producible with this repo's NamedShardings, but the helper is generic
    # over jax.Array (advisor r3).
    keys = list(seen)
    for i, a in enumerate(keys):
        for b in keys[i + 1:]:
            if all(a0 < b1 and b0 < a1 for (a0, a1), (b0, b1) in zip(a, b)):
                return None
    return seen


def local_host_copy(x) -> Optional[np.ndarray]:
    """Full host numpy copy of ``x`` assembled from addressable shards only —
    no collectives. Returns None when local shards don't cover the array.

    Replicated (and host-locally-sharded) arrays are fully reconstructable on
    every host, so gathering them never needs ``process_allgather``; that is
    what lets non-writing hosts skip checkpoint gathers entirely."""
    shards = _local_cover_shards(x)
    if shards is None:
        return None
    out = np.empty(x.shape, dtype=x.dtype)
    for bounds, sh in shards.items():
        idx = tuple(slice(a, b) for a, b in bounds)
        out[idx] = np.asarray(sh.data)
    return out


def needs_collective_gather(tree) -> bool:
    """True when gathering ``tree`` to host requires a cross-host collective
    (some leaf's data lives only on other hosts). With the standard symmetric
    NamedShardings every process computes the same answer, so it can gate who
    participates in :func:`gather_to_host`."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if (
            isinstance(leaf, jax.Array)
            and not leaf.is_fully_addressable
            and _local_cover_shards(leaf) is None
        ):
            return True
    return False


def gather_to_host(tree):
    """Device tree (possibly multi-host-sharded) -> full host numpy tree.

    Per-leaf strategy: fully-addressable -> plain device_get; replicated /
    locally-coverable -> assemble from addressable shards (no collective);
    genuinely cross-host-sharded -> ``process_allgather`` (collective — every
    process must call this function with the same tree)."""

    def gather(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            local = local_host_copy(x)
            if local is not None:
                return local
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))

    return jax.tree_util.tree_map(gather, tree)

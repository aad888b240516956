"""Pipeline parallelism over the ``pipe`` mesh axis (GPipe and 1F1B).

The encoder's layer stack is partitioned into K contiguous stages placed
on the ``pipe`` mesh dimension; the ``batch_split`` micro-batches (the
SAME micro split the gradient-accumulation scan uses,
``sharding.split_micro``) stream through the stages on a GPipe schedule:
at tick t, stage k runs micro-batch ``t - k``, so stage k's forward on
micro-batch i overlaps stage k+1's forward on micro-batch i-1. The whole
schedule is ONE ``shard_map`` island inside the jitted train step:

- each pipe rank executes only its own stage's contiguous layers per
  tick (``lax.switch`` on the rank index; params stay replicated);
- the per-tick activation hand-off to the next rank is a literal
  ``lax.ppermute`` over the ``pipe`` axis — activations cross stage
  boundaries point-to-point; rank 0 refills from the next micro-batch;
- the backward pass is plain autodiff through the tick scan: the
  ppermute transposes to the reverse permute, giving the mirrored
  backward pipeline for free, and gradients accumulate across
  micro-batches exactly as the sequential scan does (grad of the summed
  micro losses == the summed micro grads), pinning the arithmetic
  against the single-axis run.

Schedule accounting: with K stages and m micro-batches the GPipe loop
runs ``m + K - 1`` ticks of which only ``m`` are useful per stage — the
GPipe bubble fraction ``(K-1)/(K-1+m)`` (arxiv 1811.06965; MPMD
pipelining, arxiv 2412.14374). The 1F1B schedule
(:func:`make_pipeline_train_step`) interleaves one backward per forward
so a stage holds at most ``min(m, 2K-1)`` in-flight activations instead
of all m, at a ``(2K-2)/(m+2K-2)`` bubble over its combined
forward+backward tick program (TorchTitan schedules, arxiv 2410.06511).
:func:`modeled_bubble_fraction` / :func:`measured_bubble_fractions` are
the bench's efficiency instrument for both.

Stage-local state: :func:`stage_param_specs` shards each stage-scope
param leaf (embeddings + encoder layers) over ``pipe`` on a free dim, so
per-chip param/optimizer bytes drop ~1/K; the islands take the sharded
leaves as ``shard_map`` in_specs and reassemble them with EXPLICIT
``lax.all_gather`` calls (never GSPMD boundary resharding, which was
observed to miscompute on the CPU mesh).
"""

from __future__ import annotations

import functools
import logging
import re
from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .compat import shard_map

logger = logging.getLogger(__name__)


# -- schedule accounting -----------------------------------------------------

PIPE_SCHEDULES = ("gpipe", "1f1b")


def _schedule_overhead_ticks(stages: int, schedule: str) -> int:
    """Idle ticks a stage sees beyond its m useful ones: ``K-1`` warmup
    lanes for GPipe's forward program, ``2(K-1)`` (warmup + drain) for
    1F1B's combined forward+backward program."""
    if schedule not in PIPE_SCHEDULES:
        raise ValueError(
            f"unknown pipe schedule {schedule!r}; choose one of "
            f"{PIPE_SCHEDULES}"
        )
    return (stages - 1) if schedule == "gpipe" else 2 * (stages - 1)


def modeled_bubble_fraction(stages: int, microbatches: int,
                            schedule: str = "gpipe") -> float:
    """Pipeline bubble: the fraction of schedule ticks a stage spends
    idle — ``(K-1)/(K-1+m)`` for GPipe, ``(2K-2)/(2K-2+m)`` for 1F1B
    (whose tick program covers forward AND backward, so warmup and drain
    both count). 0 for a single stage."""
    stages = int(stages)
    microbatches = max(1, int(microbatches))
    c = _schedule_overhead_ticks(stages, schedule)
    if stages <= 1:
        return 0.0
    return c / (c + microbatches)


def measured_bubble_fractions(
    step_times: Mapping[int, float], stages: int,
    schedule: str = "gpipe",
) -> Dict[int, float]:
    """Measured bubble per micro-batch count from a step-time sweep.

    Each measurement at m micro-batches estimates the ideal (bubble-free)
    step time as ``T(m) * m / (m + c)`` with ``c`` the schedule's
    overhead ticks (``K-1`` GPipe, ``2(K-1)`` 1F1B) — under the schedule
    model these estimates agree across the sweep, so their median is the
    reference ideal, and ``1 - ideal / T(m)`` is the measured bubble. A
    schedule with NO real overlap (sequential stages) yields a
    near-constant measured fraction instead of the decreasing modeled
    curve, which is what the bench sweep (and its test) pins against.
    """
    stages = int(stages)
    c = _schedule_overhead_ticks(max(stages, 1), schedule)
    if stages <= 1 or not step_times:
        return {int(m): 0.0 for m in step_times}
    ideal = float(np.median([
        t * m / (m + c) for m, t in step_times.items()
    ]))
    return {
        int(m): max(0.0, 1.0 - ideal / float(t))
        for m, t in step_times.items()
    }


def stage_layer_count(num_layers: int, stages: int) -> int:
    """Layers per stage; the stack must split into K EQUAL contiguous
    stages (unequal stages would make the slowest stage the tick clock
    and silently waste the rest)."""
    stages = int(stages)
    if stages < 1:
        raise ValueError(f"pipe axis size must be >= 1, got {stages}")
    if num_layers % stages != 0:
        raise ValueError(
            f"--mesh pipe:{stages} needs the encoder depth to split into "
            f"equal contiguous stages, but {num_layers} layers % {stages} "
            f"!= 0; choose a pipe size dividing num_layers"
        )
    return num_layers // stages


def validate_pipeline_plan(plan, model, *, batch_split: int,
                           schedule: str = "gpipe") -> None:
    """Fail at construction (not at trace time) on configurations the
    pipeline runtime does not compose with yet. ``pipe x model`` IS
    composable (stage specs keep their TP dims; the island all-gathers
    both axes explicitly); ``pipe x seq`` is not."""
    cfg = getattr(model, "cfg", None)
    if cfg is None or not hasattr(cfg, "num_layers"):
        raise ValueError(
            "pipeline parallelism needs a layered encoder model "
            "(model.cfg.num_layers); got a model without one"
        )
    stage_layer_count(cfg.num_layers, plan.pipe_size)
    if schedule not in PIPE_SCHEDULES:
        raise ValueError(
            f"--pipe_schedule must be one of {PIPE_SCHEDULES}, "
            f"got {schedule!r}"
        )
    if plan.seq_size > 1:
        raise NotImplementedError(
            "--mesh with both seq and pipe axes is not composable yet: "
            "the composed streaming-ring attention path (ISSUE 20, "
            "ops/ring_attention.py) runs under its own shard_map, which "
            "cannot nest inside the pipeline island's per-tick stage "
            "compute (one shard_map cannot contain the other's "
            "collectives). Follow-up: host the ring hop loop inside the "
            "stage body so the pipe island owns both collectives."
        )
    if batch_split < 1:
        raise ValueError(f"batch_split must be >= 1, got {batch_split}")


# -- stage-local parameter layout --------------------------------------------

def stage_assignment(num_layers: int, stages: int) -> Dict[int, tuple]:
    """``{stage: (first_layer, last_layer_exclusive)}`` — which contiguous
    encoder layers each pipe rank owns. Embeddings ride with stage 0 (the
    refill rank); pooler/heads with stage K-1 (the collecting rank)."""
    S = stage_layer_count(num_layers, stages)
    return {k: (k * S, (k + 1) * S) for k in range(int(stages))}


def stage_param_specs(params, plan):
    """PartitionSpec tree sharding each stage-scope leaf (embeddings +
    encoder layers) over the ``pipe`` axis so every rank STORES ~1/K of
    the trunk — the pipeline's missing memory win. TP dims are claimed
    first (``pipe x model`` keeps its tensor-parallel specs); the pipe
    axis then lands on the leaf's largest remaining dim divisible by K
    (:func:`~.sharding._zero_leaf_plan`, the shared dim chooser, with
    ``data_size=1`` — ZeRO-1's data-axis plan is layered separately so
    it runs WITHIN the stage-local leaf set). Pooler/head leaves stay
    replicated: they run on the collected outputs outside the trunk and
    are noise next to the layer stack's bytes."""
    from .sharding import _zero_leaf_plan

    pipe_size = int(plan.pipe_size)
    has_tp = plan.model_size > 1

    def spec_for(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        return _zero_leaf_plan(
            path, shape, data_size=1, has_tp=has_tp, min_size=0,
            pipe_size=pipe_size,
        ).spec

    return jax.tree_util.tree_map_with_path(spec_for, params)


def stage_param_bytes(params, *, pipe_size: int,
                      model_size: int = 1) -> dict:
    """MODELED per-chip param bytes under the stage-local layout — no
    mesh, no devices: ``replicated_bytes`` (every leaf in full, the
    pre-stage-sharding layout), ``per_chip_bytes`` (stage-scope leaves at
    1/K — and TP leaves at 1/T — the rest in full), and ``per_stage_bytes``
    (``{stage: bytes}`` in the ownership view: embeddings with stage 0,
    each layer with its owner, pooler/heads with stage K-1) for the
    pre-flight report's stage map."""
    from .sharding import (
        MODEL_AXIS, PIPE_AXIS, STAGE_SCOPE_RE, _path_str, _zero_leaf_plan,
    )

    pipe_size = max(1, int(pipe_size))
    model_size = max(1, int(model_size))
    num_layers = len([
        k for k in params.get("transformer", {}) if k.startswith("layer_")
    ])
    owners = {}
    if num_layers and pipe_size > 1:
        for k, (lo, hi) in stage_assignment(num_layers, pipe_size).items():
            for li in range(lo, hi):
                owners[f"layer_{li}"] = k

    replicated = 0
    per_chip = 0
    per_stage = {k: 0 for k in range(pipe_size)}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        full = int(np.prod(shape or (1,), dtype=np.int64)) * dtype.itemsize
        replicated += full
        spec = _zero_leaf_plan(
            path, shape, data_size=1, has_tp=model_size > 1, min_size=0,
            pipe_size=pipe_size,
        ).spec
        shard = full
        for i, ax in enumerate(spec):
            if ax == PIPE_AXIS:
                shard //= pipe_size
            elif ax == MODEL_AXIS:
                shard //= model_size
        per_chip += shard
        path_s = _path_str(path)
        m = re.search(r"(^|/)transformer/(layer_\d+)(/|$)", path_s)
        if m and m.group(2) in owners:
            per_stage[owners[m.group(2)]] += full
        elif STAGE_SCOPE_RE.search(path_s):
            per_stage[0] += full  # embeddings feed rank 0's refill
        else:
            per_stage[pipe_size - 1] += full  # pooler/heads: last stage
    return {
        "pipe_size": pipe_size,
        "replicated_bytes": int(replicated),
        "per_chip_bytes": int(per_chip),
        "per_stage_bytes": {k: int(v) for k, v in per_stage.items()},
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _bwd_scale(x, s):
    """Identity forward, ``ct * s`` backward — the one correction the
    gathered-param islands need: stage compute is REPLICATED across the
    ``model`` axis (every TP rank runs the same gathered matmuls), so the
    all-gather transpose (psum_scatter) sums T identical param cotangents;
    scaling the gathered leaves' backward by 1/T restores the exact
    single-path gradient (exact in fp: T is a power of two)."""
    return x


def _bwd_scale_fwd(x, s):
    return x, None


def _bwd_scale_bwd(s, _, ct):
    return (jax.tree_util.tree_map(lambda c: c * s, ct),)


_bwd_scale.defvjp(_bwd_scale_fwd, _bwd_scale_bwd)


def _gather_leaf(x, spec, *, axis_sizes):
    """Reassemble one stage/TP-sharded leaf INSIDE the island with
    explicit tiled all-gathers over each mesh axis its spec names —
    manual collectives only; GSPMD resharding at the shard_map boundary
    is the known-miscompiling path this module exists to avoid. The
    transpose is psum_scatter per axis, so leaf gradients leave the
    island exactly block-sharded to match the stored layout."""
    for i, ax in enumerate(spec):
        if ax is not None and axis_sizes.get(ax, 1) > 1:
            x = jax.lax.all_gather(x, ax, axis=i, tiled=True)
    return x


def _gather_param_tree(t_params, spec_tree, *, axis_sizes):
    return jax.tree_util.tree_map(
        lambda x, s: _gather_leaf(x, s, axis_sizes=axis_sizes),
        t_params, spec_tree,
    )


# -- pipelined encoder forward ----------------------------------------------

def make_pipeline_encoder(model, plan, *, batch_split: int,
                          deterministic: bool,
                          prng_impl: str = "threefry2x32",
                          stage_specs=None):
    """Build ``encode(params, micro_inputs, base_key) -> (seq_out,
    pooled)`` running the encoder trunk on the GPipe schedule.

    ``params`` is the full (replicated) QAModel param tree;
    ``micro_inputs`` the ``[G, B_micro, ...]`` micro-split input planes
    the gradient-accumulation scan already uses (rows sharded over
    ``data`` on dim 1). Outputs are ``[G, B_micro, L, H]`` sequence
    states and ``[G, B_micro, (S,) H]`` pooled vectors — the QA heads
    and the loss run on them exactly as on the sequential path.

    The schedule is an EXPLICIT ``shard_map`` over the ``pipe`` axis
    (MPMD discipline, arxiv 2412.14374): each pipe rank runs only its
    own stage's layers per tick (``lax.switch`` on the rank index), the
    per-tick activation hand-off is a literal ``lax.ppermute`` to the
    next rank, and the collected last-stage outputs come back through
    one masked psum. Nothing is left to the auto-partitioner's choices —
    on the virtual CPU mesh, GSPMD's resharding of in-jit-stacked
    replicated params onto a ``pipe``-sharded layout was observed to
    MISCOMPUTE (see tests/test_parallel_plan.py parity pins), which is
    exactly the class of silent wrongness the explicit formulation
    removes. Rank 0 also evaluates the (cheap) embedding refill every
    tick; other ranks discard it, so its gradient flows only once.

    Dropout keys are pure functions of (base_key, micro index, global
    layer index): deterministic and resume-stable, but a DIFFERENT
    stream than the sequential path's flax module-path folding —
    pipeline trajectories are pinned against single-axis runs with
    dropout off (reduction-order tolerance), matching the DDP precedent
    that never promised cross-topology dropout determinism.

    ``stage_specs`` (a :func:`stage_param_specs` tree for the FULL param
    tree) switches on stage-local storage: the trunk leaves enter the
    island pre-sharded per spec and are reassembled with explicit tiled
    ``all_gather`` — whose transpose (psum_scatter) returns gradients
    exactly block-sharded to the stored layout. When the mesh also has a
    ``model`` axis the stage compute is replicated across TP ranks, so
    every trunk leaf's backward is scaled 1/T (:func:`_bwd_scale`) to
    cancel the replicated-cotangent psum.
    """
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    from ..models.encoder import Embeddings, EncoderLayer, _dense
    from .sharding import DATA_AXIS, PIPE_AXIS

    cfg = model.cfg
    mesh = plan.mesh
    K = int(plan.pipe_size)
    G = int(batch_split)
    S = stage_layer_count(cfg.num_layers, K)
    T = G + K - 1
    axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    model_size = int(plan.model_size)
    # a pipe-bearing mesh need not carry a data axis at all (--mesh
    # pipe:2,model:2): batch specs degrade to replicated then
    data_ax = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    trunk_specs = None if stage_specs is None else stage_specs["transformer"]

    emb_mod = Embeddings(cfg, model.dtype, model.ln_impl)
    layer_cls = EncoderLayer
    if model.remat:
        layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))
    layer_mod = layer_cls(cfg, model.dtype, model.attention_impl,
                          model.mesh, model.ln_impl, quantize=model.quantize)
    pooler_mod = _dense(model.quantize, cfg.hidden_size, name="pooler",
                        dtype=model.dtype)

    def encode(params, micro_inputs, base_key):
        t_params = params["transformer"]
        seg_starts = micro_inputs.get("segment_starts")
        has_seg = micro_inputs.get("segment_ids") is not None
        planes = {
            k: micro_inputs[k]
            for k in ("input_ids", "attention_mask", "token_type_ids",
                      "position_ids", "segment_ids")
            if micro_inputs.get(k) is not None
        }
        if "attention_mask" not in planes:
            planes["attention_mask"] = jnp.ones_like(planes["input_ids"])
        if "token_type_ids" not in planes:
            planes["token_type_ids"] = jnp.zeros_like(planes["input_ids"])
        # keys cross the shard_map boundary as raw uint32 data (extended
        # key dtypes through shard_map are version-fragile)
        kd = jax.random.key_data(base_key)

        def body(t_params, planes, kd):
            if trunk_specs is not None:
                t_params = _gather_param_tree(
                    t_params, trunk_specs, axis_sizes=axis_sizes
                )
            if model_size > 1:
                # stage compute is replicated across TP ranks — cancel
                # the T-fold cotangent psum (see _bwd_scale)
                t_params = jax.tree_util.tree_map(
                    lambda x: _bwd_scale(x, 1.0 / model_size), t_params
                )
            k_idx = jax.lax.axis_index(PIPE_AXIS)
            is_first = k_idx == 0
            base = jax.random.wrap_key_data(kd, impl=prng_impl)
            input_ids = planes["input_ids"]
            mask = planes["attention_mask"]
            ttype = planes["token_type_ids"]
            pos_ids = planes.get("position_ids")
            seg_ids = planes.get("segment_ids")
            B, Lseq = input_ids.shape[1], input_ids.shape[2]

            def micro_key(i):
                # i runs out of [0, G) on warmup/drain lanes — those keys
                # (and the activations they drop) are garbage that never
                # reaches a collected output
                return jax.random.fold_in(base, i)

            def take(x, i, *, keep=False):
                return jax.lax.dynamic_index_in_dim(
                    x, jnp.clip(i, 0, G - 1), 0, keepdims=keep
                )

            def embed_micro(i):
                return emb_mod.apply(
                    {"params": t_params["embeddings"]},
                    take(input_ids, i), take(ttype, i),
                    deterministic=deterministic,
                    position_ids=(
                        None if pos_ids is None else take(pos_ids, i)
                    ),
                    rngs={"dropout": jax.random.fold_in(micro_key(i), 0)},
                )

            def run_stage(kk, h, m, sg, micro_idx):
                # stage kk = contiguous layers [kk*S, (kk+1)*S)
                for s in range(S):
                    li = kk * S + s
                    key_l = jax.random.fold_in(
                        micro_key(micro_idx), 1 + li
                    )
                    h = layer_mod.apply(
                        {"params": t_params[f"layer_{li}"]}, h, m,
                        deterministic, sg if has_seg else None,
                        rngs={"dropout": key_l},
                    )
                return h

            def stage(h, m, sg, micro_idx):
                # each rank executes exactly ONE branch — its own stage
                branches = [
                    functools.partial(run_stage, kk) for kk in range(K)
                ]
                return jax.lax.switch(k_idx, branches, h, m, sg, micro_idx)

            h0 = embed_micro(jnp.int32(0))
            h = jnp.where(is_first, h0, jnp.zeros_like(h0))
            m = jnp.where(is_first, take(mask, jnp.int32(0)),
                          jnp.zeros_like(mask[0]))
            # the segment plane rides the rotation as a dummy when
            # packing is off (one [B, L] int buffer — cheap) so the
            # carry/switch structure is static
            seg_src = seg_ids if has_seg else mask
            sg = jnp.where(is_first, take(seg_src, jnp.int32(0)),
                           jnp.zeros_like(seg_src[0]))
            out0 = jnp.zeros((G, B, Lseq, int(cfg.hidden_size)), h0.dtype)
            perm = [(i, (i + 1) % K) for i in range(K)]

            def tick(carry, t):
                h, m, sg, out = carry
                micro_idx = t - k_idx
                y = stage(h, m, sg, micro_idx)
                # collect the LAST stage's output. Before tick K-1 the
                # write lands (clipped) on slot 0 with warmup garbage —
                # tick K-1 overwrites it with micro-batch 0's true
                # output, and every later slot is written exactly once
                # at its true tick, so no per-tick select is needed
                slot = jnp.clip(t - (K - 1), 0, G - 1)
                out = jax.lax.dynamic_update_slice(
                    out, y[None].astype(out.dtype), (slot, 0, 0, 0)
                )
                # the stage-boundary hand-off: activations (and their
                # mask/segment planes) cross to rank k+1 via collective
                # permute; rank 0 refills from the next micro-batch
                nxt = t + 1
                y_n = jax.lax.ppermute(y, PIPE_AXIS, perm)
                m_n = jax.lax.ppermute(m, PIPE_AXIS, perm)
                sg_n = jax.lax.ppermute(sg, PIPE_AXIS, perm)
                h = jnp.where(is_first, embed_micro(nxt).astype(y_n.dtype),
                              y_n)
                m = jnp.where(is_first, take(mask, nxt), m_n)
                sg = jnp.where(is_first, take(seg_src, nxt), sg_n)
                return (h, m, sg, out), None

            (_, _, _, out), _ = jax.lax.scan(
                tick, (h, m, sg, out0), jnp.arange(T, dtype=jnp.int32)
            )
            # only rank K-1 collected real outputs; the masked psum is
            # the one gather that returns them to every rank
            out = out * (k_idx == K - 1).astype(out.dtype)
            return jax.lax.psum(out, PIPE_AXIS)

        t_in_specs = P() if trunk_specs is None else trunk_specs
        seq_out = shard_map(
            body, mesh=mesh,
            in_specs=(t_in_specs, P(None, data_ax, None), P()),
            out_specs=P(None, data_ax, None, None),
            check_vma=False,
        )(t_params, planes, kd)

        # pooled output — the encoder tail (encoder.py): each row's [CLS]
        # (or each packed segment's own [CLS]) through the pooler Dense;
        # plain data-parallel compute outside the pipeline island
        if seg_starts is None:
            pool_src = seq_out[:, :, 0]
        else:
            pool_src = jnp.take_along_axis(
                seq_out, seg_starts[..., None].astype(jnp.int32), axis=2
            )
        pooled = jnp.tanh(
            pooler_mod.apply({"params": t_params["pooler"]}, pool_src)
        )
        return seq_out, pooled

    return encode


def apply_qa_heads(model, params, sequence_output, pooled_output,
                   attention_mask, *, deterministic, dropout_rng,
                   segment_ids=None, segment_starts=None):
    """The QA heads on ONE micro-batch of (pipelined) encoder outputs —
    mirrors the post-trunk body of ``QAModel.__call__`` (span logits with
    pad masking, per-segment confinement when packed, classifier on the
    dropped-out pooled vector, sigmoid regressors). Parameters are the
    same head leaves, so the two paths are interchangeable; parity with
    the sequential forward is pinned in tests/test_parallel_plan.py.
    """
    from ..models.encoder import _dense
    from ..models.qa_model import _MASK_NEG
    import flax.linen as nn

    cfg = model.cfg
    packed = segment_starts is not None

    position_logits = _dense(
        model.quantize, 2, name="position_outputs", dtype=model.dtype
    ).apply({"params": params["position_outputs"]}, sequence_output)
    start_logits = position_logits[..., 0]
    end_logits = position_logits[..., 1]

    pad_penalty = (1 - attention_mask).astype(jnp.float32) * _MASK_NEG
    start_logits = start_logits.astype(jnp.float32) + pad_penalty
    end_logits = end_logits.astype(jnp.float32) + pad_penalty

    if packed:
        S = segment_starts.shape[1]
        seg_eq = (
            segment_ids[:, None, :]
            == (1 + jnp.arange(S, dtype=segment_ids.dtype))[None, :, None]
        )
        seg_penalty = jnp.where(seg_eq, 0.0, jnp.float32(_MASK_NEG))
        start_logits = start_logits[:, None, :] + seg_penalty
        end_logits = end_logits[:, None, :] + seg_penalty

    cls_hidden = nn.Dropout(cfg.hidden_dropout_prob).apply(
        {}, pooled_output, deterministic=deterministic,
        rngs={"dropout": dropout_rng},
    )
    classifier_logits = _dense(
        model.quantize, cfg.num_labels, name="classifier", dtype=model.dtype
    ).apply({"params": params["classifier"]}, cls_hidden)

    reg_start = nn.sigmoid(
        _dense(model.quantize, 1, name="reg_start", dtype=model.dtype)
        .apply({"params": params["reg_start"]}, pooled_output)
    )[..., 0]
    reg_end = nn.sigmoid(
        _dense(model.quantize, 1, name="reg_end", dtype=model.dtype)
        .apply({"params": params["reg_end"]}, pooled_output)
    )[..., 0]

    return {
        "start_class": start_logits,
        "end_class": end_logits,
        "start_reg": reg_start.astype(jnp.float32),
        "end_reg": reg_end.astype(jnp.float32),
        "cls": classifier_logits.astype(jnp.float32),
    }


# -- 1F1B schedule ------------------------------------------------------------

def make_pipeline_train_step(model, loss, plan, *, batch_split: int,
                             prng_impl: str = "threefry2x32",
                             stage_specs=None):
    """Build ``run(params, micro_inputs, micro_labels, base_key, scale)
    -> (grads, values)``: the 1F1B tick program as ONE manual-VJP
    ``shard_map`` island (forward, heads, loss and backward all inside —
    same ppermute discipline as the GPipe island, no GSPMD boundary
    resharding anywhere).

    Schedule: at tick t stage k runs the forward of micro ``f = t - k``
    AND the backward of micro ``b = t - 2(K-1) + k`` (one-forward-one-
    backward; on the last stage b == f, so it fuses forward + heads +
    loss + backward in one tick). The program runs ``m + 2(K-1)`` ticks
    and keeps only ``W = min(m, 2K-1)`` stage inputs resident — the
    activation cap GPipe's hold-all-m schedule lacks — recomputing each
    stage forward at backward time from its saved input (bitwise
    identical: same weights, same dropout keys).

    Correctness accounting (each proved against the sequential scan):

    - backward = ``jax.vjp`` of the stage recompute seeded with the
      cotangent ppermuted back from stage k+1 (the mirrored pipeline,
      written out by hand instead of autodiff's transpose);
    - the loss is computed on FULL batch rows — local head outputs and
      labels are all-gathered over ``data`` (tiled, so row order matches
      the global batch) — because the losses' normalizers
      (valid-row counts, losses.py) are data-dependent: a local-shard
      loss would change the arithmetic. The vjp seed is ``scale / D``
      since the all-gather transpose psum-scatters D identical
      cotangents back;
    - gradients accumulate masked (``where`` selects, so warmup/drain
      garbage never taints the sum), are psum'd over ``pipe`` (stages
      own disjoint layers) and ``data`` (ranks own disjoint rows) but
      NOT ``model`` (TP ranks run identical gathered compute — summing
      would double-count; each keeps its own block), then each rank
      slices its own stage/TP block so grads leave the island exactly
      in the stored stage-local layout.
    """
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    from ..models.encoder import Embeddings, EncoderLayer, _dense
    from .sharding import DATA_AXIS, PIPE_AXIS

    cfg = model.cfg
    mesh = plan.mesh
    K = int(plan.pipe_size)
    G = int(batch_split)
    S = stage_layer_count(cfg.num_layers, K)
    W = min(G, 2 * K - 1)
    T = G + 2 * (K - 1)
    num_layers = int(cfg.num_layers)
    axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    model_size = int(plan.model_size)
    data_ax = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    data_size = axis_sizes.get(DATA_AXIS, 1)
    trunk_specs = None if stage_specs is None else stage_specs["transformer"]

    emb_mod = Embeddings(cfg, model.dtype, model.ln_impl)
    layer_cls = EncoderLayer
    if model.remat:
        layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))
    layer_mod = layer_cls(cfg, model.dtype, model.attention_impl,
                          model.mesh, model.ln_impl, quantize=model.quantize)
    pooler_mod = _dense(model.quantize, cfg.hidden_size, name="pooler",
                        dtype=model.dtype)

    def run(params, micro_inputs, micro_labels, base_key, scale):
        seg_starts = micro_inputs.get("segment_starts")
        has_seg = micro_inputs.get("segment_ids") is not None
        planes = {
            k: micro_inputs[k]
            for k in ("input_ids", "attention_mask", "token_type_ids",
                      "position_ids", "segment_ids", "segment_starts")
            if micro_inputs.get(k) is not None
        }
        if "attention_mask" not in planes:
            planes["attention_mask"] = jnp.ones_like(planes["input_ids"])
        if "token_type_ids" not in planes:
            planes["token_type_ids"] = jnp.zeros_like(planes["input_ids"])
        kd = jax.random.key_data(base_key)

        def body(params, planes, labels, kd, scale):
            t_params = params["transformer"]
            if trunk_specs is not None:
                t_params = _gather_param_tree(
                    t_params, trunk_specs, axis_sizes=axis_sizes
                )
            head_params = {
                "pooler": t_params["pooler"],
                "position_outputs": params["position_outputs"],
                "classifier": params["classifier"],
                "reg_start": params["reg_start"],
                "reg_end": params["reg_end"],
            }
            k_idx = jax.lax.axis_index(PIPE_AXIS)
            is_first = k_idx == 0
            is_last = k_idx == K - 1
            # Dropout keys in this island are pipe-rank-VARYING by
            # construction (micro index f = t - k), which rules out the
            # rbg impl: its rng_bit_generator lowering demands a
            # rank-replicated key, so XLA rewrites a varying key into a
            # select + u64 all-reduce broadcast — placed INSIDE the
            # stage-divergent switch branches, where stage 0 and stage 1
            # rendezvous on different channels and deadlock (and every
            # rank would draw identical bits besides). Threefry lowers to
            # partitionable per-element arithmetic, so the island always
            # derives threefry keys, seeding them from the caller's raw
            # key words whatever impl those came from. (The GPipe island
            # keeps the caller's impl: its micro index is the rank-uniform
            # scan counter, so its keys stay replicated and rbg is safe.)
            if prng_impl == "threefry2x32":
                base = jax.random.wrap_key_data(kd, impl=prng_impl)
            else:
                base = jax.random.key(0, impl="threefry2x32")
                for w in kd.reshape(-1):
                    base = jax.random.fold_in(base, w)
            input_ids = planes["input_ids"]
            mask = planes["attention_mask"]
            ttype = planes["token_type_ids"]
            pos_ids = planes.get("position_ids")
            seg_ids = planes.get("segment_ids")
            ss = planes.get("segment_starts")
            B, Lseq = input_ids.shape[1], input_ids.shape[2]

            def micro_key(i):
                return jax.random.fold_in(base, i)

            def take(x, i, *, keep=False):
                return jax.lax.dynamic_index_in_dim(
                    x, jnp.clip(i, 0, G - 1), 0, keepdims=keep
                )

            def embed_with(e_params, i):
                return emb_mod.apply(
                    {"params": e_params},
                    take(input_ids, i), take(ttype, i),
                    deterministic=False,
                    position_ids=(
                        None if pos_ids is None else take(pos_ids, i)
                    ),
                    rngs={"dropout": jax.random.fold_in(micro_key(i), 0)},
                )

            def run_stage(kk, tp, h, m, sg, micro_idx):
                for s in range(S):
                    li = kk * S + s
                    key_l = jax.random.fold_in(micro_key(micro_idx), 1 + li)
                    h = layer_mod.apply(
                        {"params": tp[f"layer_{li}"]}, h, m,
                        False, sg if has_seg else None,
                        rngs={"dropout": key_l},
                    )
                return h

            def stage(tp, h, m, sg, micro_idx):
                branches = [
                    functools.partial(run_stage, kk) for kk in range(K)
                ]
                return jax.lax.switch(
                    k_idx, branches, tp, h, m, sg, micro_idx
                )

            def head_loss(hp, y, micro_idx):
                # heads + loss for ONE micro-batch, on FULL batch rows
                # (see docstring: the loss normalizers are data-dependent)
                if ss is None:
                    src = y[:, 0]
                    ss_i = None
                else:
                    ss_i = take(ss, micro_idx)
                    src = jnp.take_along_axis(
                        y, ss_i[..., None].astype(jnp.int32), axis=1
                    )
                pooled = jnp.tanh(
                    pooler_mod.apply({"params": hp["pooler"]}, src)
                )
                preds = apply_qa_heads(
                    model, hp, y, pooled, take(mask, micro_idx),
                    deterministic=False,
                    dropout_rng=jax.random.fold_in(
                        micro_key(micro_idx), 1 + num_layers
                    ),
                    segment_ids=(
                        take(seg_ids, micro_idx) if has_seg else None
                    ),
                    segment_starts=ss_i,
                )
                lab = jax.tree_util.tree_map(
                    lambda x: take(x, micro_idx), labels
                )
                if data_ax is not None and data_size > 1:
                    preds = jax.tree_util.tree_map(
                        lambda x: jax.lax.all_gather(
                            x, data_ax, axis=0, tiled=True
                        ), preds,
                    )
                    lab = jax.tree_util.tree_map(
                        lambda x: jax.lax.all_gather(
                            x, data_ax, axis=0, tiled=True
                        ), lab,
                    )
                total_i, values_i = loss(preds, lab)
                return total_i, values_i

            def masked_add(acc, contrib, valid):
                return jax.tree_util.tree_map(
                    lambda a, g: a + jnp.where(
                        valid, g, jnp.zeros_like(g)
                    ).astype(a.dtype),
                    acc, contrib,
                )

            h0 = embed_with(t_params["embeddings"], jnp.int32(0))
            h_init = jnp.where(is_first, h0, jnp.zeros_like(h0))
            zeros_f32 = functools.partial(
                jax.tree_util.tree_map,
                lambda x: jnp.zeros(jnp.shape(x), jnp.float32),
            )
            carry0 = (
                h_init,
                jnp.zeros_like(h0),                       # g_ct
                jnp.zeros((W,) + h0.shape, h0.dtype),     # in_buf
                zeros_f32(t_params),                      # acc_stage
                zeros_f32(t_params["embeddings"]),        # acc_emb
                zeros_f32(head_params),                   # acc_head
                zeros_f32(loss.value_structure()),        # v_acc
            )
            perm_fwd = [(i, (i + 1) % K) for i in range(K)]
            perm_bwd = [(i, (i - 1) % K) for i in range(K)]

            def tick(carry, t):
                h, g_ct, in_buf, acc_stage, acc_emb, acc_head, v_acc = carry
                f = t - k_idx
                b = t - 2 * (K - 1) + k_idx
                f_valid = (f >= 0) & (f < G)
                b_valid = (b >= 0) & (b < G)
                fc = jnp.clip(f, 0, G - 1)
                bc = jnp.clip(b, 0, G - 1)

                # -- forward unit: micro f through this rank's stage
                m_f = take(mask, fc)
                seg_src = seg_ids if has_seg else mask
                sg_f = take(seg_src, fc)
                y = stage(t_params, h, m_f, sg_f, fc)
                # save the stage INPUT for recompute at backward time;
                # masked write so warmup/drain lanes never clobber a
                # live slot (W >= the in-flight window, so micro f-W is
                # fully drained before its slot is reused)
                slot_f = jnp.mod(fc, W)
                cur = jax.lax.dynamic_index_in_dim(
                    in_buf, slot_f, 0, keepdims=False
                )
                in_buf = jax.lax.dynamic_update_slice(
                    in_buf,
                    jnp.where(f_valid, h, cur)[None],
                    (slot_f,) + (0,) * h.ndim,
                )

                # -- heads + loss (every rank computes it on its y so the
                # collectives inside stay uniform; only the last stage's
                # result is real — everything else is masked out)
                (_, head_vjp, values_i) = jax.vjp(
                    lambda hp, yy: head_loss(hp, yy, fc),
                    head_params, y, has_aux=True,
                )
                d_hp, d_y = head_vjp(
                    (scale / data_size).astype(jnp.float32)
                )

                # -- backward unit: recompute micro b's stage forward
                # from the saved input, transpose with jax.vjp
                h_saved = jax.lax.dynamic_index_in_dim(
                    in_buf, jnp.mod(bc, W), 0, keepdims=False
                )
                m_b = take(mask, bc)
                sg_b = take(seg_src, bc)
                _, stage_vjp = jax.vjp(
                    lambda tp, hh: stage(tp, hh, m_b, sg_b, bc),
                    t_params, h_saved,
                )
                ct_in = jnp.where(is_last, d_y, g_ct).astype(h.dtype)
                d_tp, d_h = stage_vjp(ct_in)
                # rank 0's stage input was the embedding output: push the
                # incoming cotangent through the embed recompute
                _, emb_vjp = jax.vjp(
                    lambda ep: embed_with(ep, bc), t_params["embeddings"]
                )
                (d_emb,) = emb_vjp(d_h.astype(h0.dtype))

                acc_stage = masked_add(acc_stage, d_tp, b_valid)
                acc_emb = masked_add(acc_emb, d_emb, b_valid & is_first)
                acc_head = masked_add(acc_head, d_hp, f_valid & is_last)
                v_acc = masked_add(v_acc, values_i, f_valid & is_last)

                # -- hand-offs: activations forward, cotangents backward
                y_n = jax.lax.ppermute(y, PIPE_AXIS, perm_fwd)
                g_ct = jax.lax.ppermute(d_h, PIPE_AXIS, perm_bwd)
                h = jnp.where(
                    is_first,
                    embed_with(t_params["embeddings"], t + 1).astype(
                        y_n.dtype
                    ),
                    y_n,
                )
                return (h, g_ct, in_buf, acc_stage, acc_emb, acc_head,
                        v_acc), None

            (_, _, _, acc_stage, acc_emb, acc_head, v_acc), _ = (
                jax.lax.scan(
                    tick, carry0, jnp.arange(T, dtype=jnp.int32)
                )
            )

            # stages own disjoint layers, data ranks disjoint rows; model
            # ranks ran IDENTICAL compute — no psum there (see docstring)
            grad_axes = tuple(
                a for a in (PIPE_AXIS, data_ax) if a is not None
            )
            acc_stage = jax.lax.psum(acc_stage, grad_axes)
            acc_emb = jax.lax.psum(acc_emb, grad_axes)
            acc_head = jax.lax.psum(acc_head, grad_axes)
            values = jax.lax.psum(v_acc, PIPE_AXIS)

            g_trans = dict(acc_stage)
            g_trans["embeddings"] = acc_emb
            g_trans["pooler"] = acc_head["pooler"]
            grads = {
                "transformer": g_trans,
                "position_outputs": acc_head["position_outputs"],
                "classifier": acc_head["classifier"],
                "reg_start": acc_head["reg_start"],
                "reg_end": acc_head["reg_end"],
            }
            if stage_specs is not None:
                def slice_own(g, spec):
                    for i, ax in enumerate(spec):
                        if ax is not None and axis_sizes.get(ax, 1) > 1:
                            size = g.shape[i] // axis_sizes[ax]
                            g = jax.lax.dynamic_slice_in_dim(
                                g, jax.lax.axis_index(ax) * size, size,
                                axis=i,
                            )
                    return g

                grads = jax.tree_util.tree_map(
                    slice_own, grads, stage_specs
                )
            return grads, values

        p_in_specs = P() if stage_specs is None else stage_specs
        g_out_specs = P() if stage_specs is None else stage_specs
        grads, values = shard_map(
            body, mesh=mesh,
            in_specs=(p_in_specs, P(None, data_ax, None),
                      P(None, data_ax), P(), P()),
            out_specs=(g_out_specs, P()),
            check_vma=False,
        )(params, planes, micro_labels, kd, jnp.asarray(scale, jnp.float32))
        return grads, values

    return run

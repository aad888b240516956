"""The train step as a program: ``StepSpec -> step``.

``build_step(spec)`` returns the function the ``Trainer`` jits:
``(params, opt_state, inputs, labels, step) -> (params, opt_state, values)``,
forward + loss + gradient accumulation over ``batch_split`` micro-batches +
clip + optimizer update in one compiled program. Everything it needs is in
the frozen ``StepSpec``; nothing here knows the ``Trainer`` (the QA heads are
one client: any model with ``apply``, any loss with ``value_structure`` /
``denominators``, any optax optimizer).

Three decisions live here, each in one place:

- the layout of the accumulated f32 gradient (``GradCarry``: per tensor on
  every mesh; one vector a ZeRO-1 bucket only where ``--zero1_overlap
  bucketed`` engages), chosen by ``choose_carry`` from what it can observe;
- where the gradients cross the mesh (``exchanges_once``): on a mesh whose
  only axis wider than 1 is ``data``, with several micro-batches a step, the
  loop runs as a data island and the chips exchange ONE accumulated gradient
  after it; otherwise plain GSPMD finishes every micro-batch's gradients
  with an all-reduce;
- how ``(gradients, values)`` come about: ``micro_loop``, ``island_loop``,
  ``gpipe`` or ``one_f_one_b``, four functions with one signature between one
  prologue and one epilogue (``build_step``).

Scope names (``forward_backward``, ``loss``, ``grad_accumulate``,
``grad_reduce``, ``grad_clip``, ``optimizer``, ``step_metrics``) are what the
trace readers attribute device time by (``metrics/trace.scope_map``).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import (
    DATA_AXIS,
    leaf_sizes,
    zero1_bucket_plan,
    zero_pad_tree,
    zero_unpad_tree,
)
from . import loss_scale as ls_lib

logger = logging.getLogger(__name__)


# -- the accumulated gradient's layout -------------------------------------------

class GradCarry:
    """How a step holds the f32 gradient it accumulates over micro-batches.
    Built inside the traced step from the parameters it was handed (shapes
    and dtypes only) and the trainable mask (a bool a leaf, None = all).

    ``zeros()`` / ``add(acc, grads)`` are the scan's carry, ``from_tree`` takes
    a whole-batch gradient tree (the pipelined bodies produce one),
    ``mask_frozen`` / ``sq_norm`` serve the clip on the layout itself and
    ``to_tree(acc, params)`` hands the optimizer a tree in the parameters'
    dtypes. A frozen module's gradient is replaced (``where`` / static zeros),
    not multiplied: its inf/nan must vanish rather than poison the norm or
    trip the finite check for parameters that are not even optimized.

    This base is the per-tensor layout, which every mesh runs: each gradient
    stays in its parameter's shape, tiling and sharding, so the add fuses
    into the weight-gradient fusion that produced it and no copy of the whole
    gradient is made. On the chip, a step of four micro-batches: 1.3 ms for
    bert-base's 0.44 GB and 2.3 ms for bert-large's 1.34 GB (10 ms/GB where
    gradients come out of custom calls), where one flat vector, ravelled out
    of every leaf's tiling and concatenated each micro-batch, cost 22.5 ms
    and 55 ms in its own scope and more outside it (PERF.md section 6,
    PR 30)."""

    name = "per_tensor"     # what the pre-flight report says under grad_carry

    def __init__(self, params, trainable=None, buckets=()):
        self.params = params
        self.sizes = leaf_sizes(params)
        self.trainable = trainable
        self.buckets = buckets

    def zeros(self):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), self.params
        )

    def add(self, acc, grads):
        return jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads
        )

    def from_tree(self, grads):
        return jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads
        )

    def mask_frozen(self, acc):
        if self.trainable is None:
            return acc
        return jax.tree_util.tree_map(
            lambda g, m: g if m else jnp.zeros_like(g), acc, self.trainable
        )

    def sq_norm(self, acc):
        return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(acc))

    def to_tree(self, acc, params):
        return jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), acc, params
        )


class BucketedCarry(GradCarry):
    """One f32 vector PER BUCKET (``--zero1_overlap bucketed``). Buckets are
    contiguous leaf runs, so concatenating the bucket vectors reproduces the
    ravelled gradient element for element: every consumer runs the same
    arithmetic while each bucket's reduce-scatter depends only on its own
    carry. (The programs still partition differently under GSPMD, so
    trajectories agree to reduction-order tolerance, not bitwise.)"""

    name = "bucketed"

    def zeros(self):
        return tuple(
            jnp.zeros((int(b.size),), jnp.float32) for b in self.buckets
        )

    def add(self, acc, grads):
        return tuple(a + f for a, f in zip(acc, self.from_tree(grads)))

    def from_tree(self, grads):
        g_leaves = jax.tree_util.tree_leaves(grads)
        return tuple(
            jnp.concatenate(
                [
                    jnp.ravel(g_leaves[k]).astype(jnp.float32)
                    for k in range(bk.lo, bk.hi)
                ]
            )
            for bk in self.buckets
        )

    def mask_frozen(self, acc):
        if self.trainable is None:
            return acc
        return tuple(
            jnp.where(self._flat_mask(bk.lo, bk.hi), gvec, 0.0)
            for bk, gvec in zip(self.buckets, acc)
        )

    def sq_norm(self, acc):
        # over the CONCATENATION: one reduce over every element; the scalar
        # is the only cross-bucket dependency (inherent to global-norm
        # clipping), and it is one f32
        full = jnp.concatenate(acc)
        return jnp.sum(full * full)

    def to_tree(self, acc, params):
        return self._unflatten(
            [
                (acc[bi], sum(self.sizes[bk.lo:k]))
                for bi, bk in enumerate(self.buckets)
                for k in range(bk.lo, bk.hi)
            ],
            params,
        )

    def _flat_mask(self, lo, hi):
        mask = jax.tree_util.tree_leaves(self.trainable)
        return jnp.concatenate(
            [jnp.full((self.sizes[k],), bool(mask[k])) for k in range(lo, hi)]
        )

    def _unflatten(self, slices, params):
        """``slices``: a ``(vector, offset)`` a leaf, in ``tree_leaves``
        order."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                jax.lax.dynamic_slice_in_dim(vec, off, self.sizes[k])
                .reshape(leaves[k].shape)
                .astype(leaves[k].dtype)
                for k, (vec, off) in enumerate(slices)
            ],
        )


def choose_carry(plan, params, *, zero_plan=None, overlap="off",
                 bucket_mb=4.0):
    """``(layout class, buckets)`` for a step under ``plan``: per tensor on
    every mesh. Bucketed only where ``overlap="bucketed"`` meets replicated
    gradients (no ``model`` axis; stage-local storage exists only under
    ``pipe``) AND ZeRO-1 shards AND a sequential scan accumulates (no
    ``pipe`` axis); elsewhere it is inert, and says why."""
    if overlap != "bucketed":
        return GradCarry, ()
    if zero_plan is None:
        logger.info(
            "zero1_overlap=bucketed without an active zero1 layout "
            "(--optimizer_sharding off or a 1-chip mesh): nothing to "
            "bucket; the monolithic step runs unchanged."
        )
    elif plan.pipe_size > 1:
        logger.info(
            "zero1_overlap=bucketed under pipeline parallelism: the "
            "pipelined backward yields the full gradient at once "
            "(no accumulation carry to overlap); bucketing is inert."
        )
    elif plan.model_size > 1:
        logger.info(
            "zero1_overlap=bucketed where gradients are sharded (a "
            "tensor-parallel mesh): each accumulates in its parameter's "
            "sharding, maximal per-leaf independence already; bucketing "
            "is inert."
        )
    else:
        buckets = tuple(zero1_bucket_plan(params, bucket_mb=bucket_mb))
        logger.info(
            "ZeRO-1 overlap: %d gradient bucket(s) at ~%.1f MB "
            "target (per-bucket reduce-scatter / all-gather "
            "independently schedulable).", len(buckets), float(bucket_mb),
        )
        return BucketedCarry, buckets
    return GradCarry, ()


# -- what a step is built from ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepSpec:
    """Everything ``build_step`` closes over. The shardings are all None
    when their mechanism is off (ZeRO-1; stage-local pipeline storage; a
    one-device mesh has no ``param_shardings``)."""

    model: Any
    loss: Any
    optimizer: Any
    plan: Any                           # parallel.plan.ParallelPlan
    batch_split: int = 1
    seed: int = 0
    prng_impl: str = "rbg"
    carry: type = GradCarry             # choose_carry's pair
    buckets: tuple = ()
    scheduler: Any = None
    schedule_count: Any = None          # opt_state -> the schedule's count
    use_loss_scale: bool = False
    # the optimizer chain is built without clip_by_global_norm: the step
    # clips on the carry's layout, after the frozen modules are zeroed
    max_grad_norm: Optional[float] = None
    trainable: Any = None               # optim.trainable_mask (None = all)
    zero_plan: Any = None
    zero_param_shardings: Any = None
    zero_state_shardings: Any = None
    param_shardings: Any = None
    stage_param_specs: Any = None
    opt_state_shardings: Any = None
    pipe_schedule: str = "gpipe"


def exchanges_once(spec: StepSpec) -> bool:
    """True where the micro-batch loop runs as a data island
    (``island_loop``). Kept on the GSPMD body: one micro-batch a step
    (nothing to save), tensor/sequence/pipeline meshes (their own bodies),
    and the partitionable threefry generator, whose hidden-dropout masks are
    a function of the logical index alone and so mesh-invariant under GSPMD:
    a promise (test_dp8_matches_single_device_with_threefry_dropout) the
    island's per-chip draws would break."""
    return (
        spec.plan.data_only
        and spec.batch_split > 1
        and spec.prng_impl != "threefry2x32"
    )


# -- after the gradients: clip, update, metrics -----------------------------------

def clip_gradients(spec, carry, acc_grads, params, ls_state):
    """Mean over micro-batches, frozen modules zeroed, loss-scale
    unscale/finite-check and global-norm clip, all on the carry's layout;
    ``(gradient tree in the parameters' dtypes, finite or None)``. Semantics
    match torch ``clip_grad_norm_`` over the OPTIMIZED params (frozen ones
    are zeroed first); overflow steps contribute zero grads so the update
    is a no-op."""
    inv = 1.0 / spec.batch_split
    clip_norm = spec.max_grad_norm
    with jax.named_scope("grad_clip"):
        grads = jax.tree_util.tree_map(lambda g: g * inv, acc_grads)
        grads = carry.mask_frozen(grads)
        finite = None
        if spec.use_loss_scale:
            grads = ls_lib.unscale(grads, ls_state)
            finite = ls_lib.all_finite(grads)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(finite, g, 0.0), grads
            )
        if clip_norm is not None and clip_norm > 0:
            # optax.clip_by_global_norm semantics: g * c / max(norm, c)
            gnorm = jnp.sqrt(carry.sq_norm(grads))
            scale = clip_norm / jnp.maximum(gnorm, clip_norm)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    with jax.named_scope("grad_accumulate"):
        return carry.to_tree(grads, params), finite


def apply_update(spec, params, opt_state, grads):
    """The optimizer update, ``(new params, new optimizer state)``."""
    optimizer = spec.optimizer
    stage_mode = spec.stage_param_specs is not None
    with jax.named_scope("optimizer"):
        if spec.zero_plan is not None:
            # ZeRO-1 (--optimizer_sharding zero1): pad grads and params into
            # the per-leaf plan layout and CONSTRAIN them onto the data axis.
            # GSPMD then lowers the gradient reduction as a reduce-scatter
            # (each replica receives only its shard's sum) and the update
            # touches 1/N of the elements per chip against the 1/N-resident
            # moments; the updates are sliced back to logical shapes and
            # applied to the replicated params, which is the trailing
            # all-gather of the ZeRO-1 pattern (arxiv 2004.13336).
            with jax.named_scope("grad_reduce"):
                grads_p = jax.lax.with_sharding_constraint(
                    zero_pad_tree(grads, spec.zero_plan),
                    spec.zero_param_shardings,
                )
            params_p = jax.lax.with_sharding_constraint(
                zero_pad_tree(params, spec.zero_plan),
                spec.zero_param_shardings,
            )
            updates_p, new_opt_state = optimizer.update(
                grads_p, opt_state, params_p
            )
            # keep the ZeRO layout stable across steps: without the
            # constraint GSPMD may re-layout the donated state to match
            # whatever the update fusion preferred
            new_opt_state = jax.lax.with_sharding_constraint(
                new_opt_state, spec.zero_state_shardings
            )
            updates = zero_unpad_tree(updates_p, spec.zero_plan, params)
        else:
            updates, new_opt_state = optimizer.update(
                grads, opt_state, params
            )
            if stage_mode and spec.opt_state_shardings is not None:
                # keep the stage-local moments pipe-sharded across steps
                new_opt_state = jax.lax.with_sharding_constraint(
                    new_opt_state, spec.opt_state_shardings
                )
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates
        )
        if (spec.zero_plan is not None or stage_mode) \
                and spec.param_shardings is not None:
            # pin the updated params to the params' own (replicated, TP, or
            # stage-local) layout so the donated buffers keep their shape
            new_params = jax.lax.with_sharding_constraint(
                new_params, spec.param_shardings
            )
    return new_params, new_opt_state


def finish_step(spec, carry, params, opt_state, acc_grads, values, step,
                ls_state):
    """Everything after gradient accumulation, the same for every body, so
    the pipelined paths cannot drift from the sequential arithmetic."""
    use_ls = spec.use_loss_scale
    grads, finite = clip_gradients(spec, carry, acc_grads, params, ls_state)
    new_params, new_opt_state = apply_update(spec, params, opt_state, grads)

    with jax.named_scope("step_metrics"):
        # lr APPLIED this step: optax scale_by_schedule reads
        # schedule(count) pre-increment. Without loss scaling count ==
        # step; with it, overflow steps are skipped (count freezes), so
        # read the actual count out of the incoming optimizer state.
        if spec.scheduler is None:
            values["lr"] = jnp.float32(0)
        elif use_ls and spec.schedule_count is not None:
            values["lr"] = spec.scheduler(spec.schedule_count(opt_state))
        else:
            values["lr"] = spec.scheduler(step)

    if not use_ls:
        return new_params, new_opt_state, values
    # apex semantics: on overflow, skip the whole update (params, moments,
    # schedule count) and back off the scale
    with jax.named_scope("optimizer"):
        new_params = ls_lib.masked_update(new_params, params, finite)
        new_opt_state = ls_lib.masked_update(new_opt_state, opt_state, finite)
        ls_state = ls_lib.update_state(ls_state, finite)
    with jax.named_scope("step_metrics"):
        values["loss_scale"] = ls_state.scale
        values["grads_finite"] = finite.astype(jnp.float32)
    return new_params, ls_lib.OptStateWithLS(new_opt_state, ls_state), values


# -- the gradients: four bodies, one signature -------------------------------------
#
# body(spec, carry, params, inputs, labels, base, ls_state) -> (grads, values)
#
# ``inputs`` / ``labels`` are the stacked micro-batches ([G, B, ...]),
# ``base`` the step's key (a pure function of seed and step). ``values`` are
# the loss values SUMMED over micro-batches. The two loops hand back the
# accumulated gradient in the carry's layout; the two pipelined bodies the
# whole batch's gradient tree, which one backward pass produces.

def micro_loop(spec, carry, params, inputs, labels, base, ls_state,
               rngs=None, denoms=None):
    """The gradient-accumulation scan over the stacked micro-batches, and as
    it stands the body under plain ``jit``/GSPMD: one device, one micro-batch
    a step, threefry, every mesh that is not data-only. There the rows are
    the global micro-batch's, ``base`` is split into one "dropout" key a
    micro-batch and ``denoms`` is None. Inside the data island the rows are
    one chip's, ``rngs`` brings flax's rng names with one key a micro-batch
    each, and ``denoms`` (one entry a micro-batch) the global micro-batch's
    loss normalisers."""
    if rngs is None:
        rngs = {"dropout": jax.random.split(base, spec.batch_split)}
    model = spec.model
    # a trunk's own counters (expert routing) ride the loss values out of
    # the step; the encoder has none
    stat_keys = tuple(getattr(model, "step_stat_keys", ()))

    def stat_scale(key, den):
        """What a micro-batch's counter is multiplied by so that the step's
        value, which is summed over micro-batches and (in the island, where
        ``den`` is given) over chips and then divided by ``batch_split``, is
        the step's SUM for a count and the mean over micro-batches and chips
        for a ratio."""
        if key in model.step_stat_sums:
            return float(spec.batch_split)
        return 1.0 / spec.plan.data_size if den is not None else 1.0

    def loss_fn(p, micro_in, micro_lab, micro_rngs, den):
        apply = model.apply_with_stats if stat_keys else model.apply
        out = apply(
            {"params": p}, **micro_in, deterministic=False, rngs=micro_rngs,
        )
        preds, stats = out if stat_keys else (out, {})
        with jax.named_scope("loss"):
            total, values = spec.loss(preds, micro_lab, den)
            if spec.use_loss_scale:
                # scale inside the grad; reported `values` stay unscaled
                total = ls_lib.scale_loss(total, ls_state)
        if stats:
            with jax.named_scope("step_metrics"):
                values = {**values, **{
                    k: jax.lax.stop_gradient(v) * stat_scale(k, den)
                    for k, v in stats.items()}}
        return total, values

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def micro_step(state, xs):
        g_acc, v_acc = state
        micro_in, micro_lab, micro_rngs, den = xs
        with jax.named_scope("forward_backward"):
            (_, values), grads = grad_fn(
                params, micro_in, micro_lab, micro_rngs, den
            )
        with jax.named_scope("grad_accumulate"):
            g_acc = carry.add(g_acc, grads)
        with jax.named_scope("step_metrics"):
            v_acc = jax.tree_util.tree_map(jnp.add, v_acc, values)
        return (g_acc, v_acc), None

    v0 = jax.tree_util.tree_map(
        lambda _: jnp.zeros((), jnp.float32),
        {**spec.loss.value_structure(), **dict.fromkeys(stat_keys, 0.0)},
    )
    with jax.named_scope("grad_accumulate"):
        state = (carry.zeros(), v0)
    return jax.lax.scan(micro_step, state, (inputs, labels, rngs, denoms))[0]


def island_loop(spec, carry, params, inputs, labels, base, ls_state):
    """``micro_loop`` as a data island: one ``shard_map`` over ``data``
    round the scan and nothing else. Each chip accumulates the unreduced
    gradient sum of its own rows, and ONE f32 sum over the chips follows the
    island, where plain GSPMD finishes every micro-batch's weight gradients
    with an all-reduce before a replicated carry may add them. What makes
    the chips' sums add up to the global gradient:

    - every loss term's normaliser (valid rows, class weights, row count) is
      the GLOBAL micro-batch's, taken from the labels before the loop under
      GSPMD (``loss.denominators``), so a chip's value is its share and no
      collective stands in the loop;
    - hidden dropout draws over the chip's own rows, so each chip folds its
      ``data`` index into the micro-batch key it hands flax as "dropout"
      (the default ``rbg`` masks never were mesh-invariant);
    - attention draws from the UNfolded key ("attention_dropout") and sees
      the manual axis: the kernels take the chip's rows directly, dropout
      seeds by global row, and XLA attention takes the chip's rows of the
      micro-batch's draw, so attention masks stay those of one device
      (``ops/attention.py``)."""
    keys = jax.random.split(base, spec.batch_split)
    with jax.named_scope("loss"):
        denoms = jax.vmap(spec.loss.denominators)(labels)

    def island(params, inputs, labels, key_data, ls_state, denoms):
        chip = jax.lax.axis_index(DATA_AXIS)
        keys = jax.random.wrap_key_data(key_data, impl=spec.prng_impl)
        rngs = {
            "dropout": jax.vmap(
                lambda k: jax.random.fold_in(k, chip))(keys),
            "attention_dropout": keys,
        }
        return micro_loop(
            spec, carry, params, inputs, labels, None, ls_state, rngs, denoms
        )

    # keys cross the boundary as raw words (pipeline.py's discipline)
    per_chip = spec.plan.data_island(
        island, row_args=(False, True, True, False, False, False),
    )(params, inputs, labels, jax.random.key_data(keys), ls_state, denoms)
    # the chips' carries come back stacked on a leading `data` axis; their
    # sum is GSPMD's to place: an all-reduce, or under ZeRO-1 the
    # reduce-scatter apply_update's constraint asks for
    with jax.named_scope("grad_reduce"):
        return jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), per_chip)


def gpipe(spec, carry, params, inputs, labels, base, ls_state):
    """``--mesh pipe:K`` on the GPipe schedule: the encoder trunk runs the
    micro-batches through K contiguous layer stages
    (``parallel/pipeline.py``); heads + loss run per micro-batch on the
    collected outputs, and the gradient of the summed micro losses IS the
    accumulated gradient the sequential scan produces."""
    from ..parallel.pipeline import apply_qa_heads, make_pipeline_encoder

    model, loss = spec.model, spec.loss
    pipe_encode = make_pipeline_encoder(
        model, spec.plan, batch_split=spec.batch_split, deterministic=False,
        prng_impl=spec.prng_impl, stage_specs=spec.stage_param_specs,
    )
    num_layers = int(model.cfg.num_layers)

    def loss_fn(p):
        seq_out, pooled = pipe_encode(p, inputs, base)
        v_acc = jax.tree_util.tree_map(
            lambda _: jnp.zeros((), jnp.float32), loss.value_structure(),
        )
        total = jnp.float32(0)
        for i in range(spec.batch_split):
            micro_in = jax.tree_util.tree_map(lambda x: x[i], inputs)
            micro_lab = jax.tree_util.tree_map(lambda x: x[i], labels)
            am = micro_in.get("attention_mask")
            if am is None:
                am = jnp.ones_like(micro_in["input_ids"])
            preds = apply_qa_heads(
                model, p, seq_out[i], pooled[i], am, deterministic=False,
                # head-dropout key: (base, micro, 1+num_layers), disjoint
                # from the embed (0) and layer (1..num_layers) folds the
                # encoder uses
                dropout_rng=jax.random.fold_in(
                    jax.random.fold_in(base, i), 1 + num_layers
                ),
                segment_ids=micro_in.get("segment_ids"),
                segment_starts=micro_in.get("segment_starts"),
            )
            t_i, values_i = loss(preds, micro_lab)
            total = total + t_i
            v_acc = jax.tree_util.tree_map(jnp.add, v_acc, values_i)
        if spec.use_loss_scale:
            # scaling the summed loss == scaling each micro loss
            # (linearity), the sequential path's arithmetic
            total = ls_lib.scale_loss(total, ls_state)
        return total, v_acc

    (_, values), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return grads, values


def one_f_one_b(spec, carry, params, inputs, labels, base, ls_state):
    """``--pipe_schedule 1f1b``: forward, heads, loss AND backward run
    inside one manual-VJP island (``parallel/pipeline.py``) whose grads are
    proven equal to the sequential scan's. Activation residency is capped at
    the in-flight window instead of all micro-batches."""
    from ..parallel.pipeline import make_pipeline_train_step

    pipe_run = make_pipeline_train_step(
        spec.model, spec.loss, spec.plan, batch_split=spec.batch_split,
        prng_impl=spec.prng_impl, stage_specs=spec.stage_param_specs,
    )
    scale = ls_state.scale if spec.use_loss_scale else jnp.float32(1.0)
    return pipe_run(params, inputs, labels, base, scale)


def build_step(spec: StepSpec):
    """The step function for ``jax.jit(step, donate_argnums=(0, 1))``. Its
    ``__name__`` (``train_step`` / ``train_step_pipe``) is the program's
    name in a trace (``jit_<name>``), which the trace readers find it by."""
    pipe = spec.plan.pipe_size > 1
    if pipe:
        gradients = one_f_one_b if spec.pipe_schedule == "1f1b" else gpipe
    else:
        gradients = island_loop if exchanges_once(spec) else micro_loop
    inv = 1.0 / spec.batch_split

    def train_step(params, opt_state, inputs, labels, step):
        ls_state = None
        if spec.use_loss_scale:
            opt_state, ls_state = opt_state.inner, opt_state.ls
        carry = spec.carry(params, spec.trainable, spec.buckets)
        # per-step dropout keys: pure function of (seed, step, micro-index)
        base = jax.random.fold_in(
            jax.random.key(spec.seed, impl=spec.prng_impl), step
        )
        grads, values = gradients(
            spec, carry, params, inputs, labels, base, ls_state
        )
        with jax.named_scope("step_metrics"):
            values = jax.tree_util.tree_map(lambda v: v * inv, values)
        if pipe:
            with jax.named_scope("grad_accumulate"):
                grads = carry.from_tree(grads)
        return finish_step(
            spec, carry, params, opt_state, grads, values, step, ls_state
        )

    if pipe:
        train_step.__name__ = train_step.__qualname__ = "train_step_pipe"
    return train_step

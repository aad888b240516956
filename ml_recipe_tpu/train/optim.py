"""Optimizers and LR schedule (optax).

Parity targets:
- HF ``AdamW(..., correct_bias=False)`` + no-decay param groups for
  bias/LayerNorm (reference init.py:125-138): here one optax chain with a
  decay mask over param paths.
- ``AdaMod`` (reference trainer/optim.py:8-100, vendored from
  lancopku/AdaMod): Adam moments plus an EMA bound on the per-parameter step
  size — re-derived as an optax GradientTransformation.
- ``get_linear_schedule_with_warmup`` (reference trainer.py:116-126): linear
  0→lr over warmup, then linear decay to 0 at num_training_steps.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


def linear_warmup_schedule(lr: float, num_warmup_steps: int, num_training_steps: int):
    """LR(step): step/warmup * lr, then linear decay to 0 (HF semantics)."""

    def schedule(step):
        step = jnp.asarray(step, dtype=jnp.float32)
        warm = jnp.maximum(num_warmup_steps, 1)
        rise = step / warm
        fall = jnp.maximum(
            (num_training_steps - step)
            / jnp.maximum(num_training_steps - num_warmup_steps, 1),
            0.0,
        )
        return lr * jnp.where(step < num_warmup_steps, rise, fall)

    return schedule


class AdaModState(NamedTuple):
    count: jnp.ndarray
    exp_avg: optax.Updates
    exp_avg_sq: optax.Updates
    exp_avg_lr: optax.Updates


def adamod(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    beta3: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    decay_mask=None,
    mask=None,
) -> optax.GradientTransformation:
    """AdaMod: Adam with momental bounds on per-param learning rates.

    Matches the reference implementation step-for-step (trainer/optim.py:73-98):
    bias-corrected Adam step size per element, EMA-smoothed (beta3) upper
    bound, decoupled weight decay applied as ``p -= wd * lr * p``.
    ``decay_mask`` (True = decay) reproduces the reference's no-decay param
    groups for bias/LayerNorm (init.py:124-128).
    """

    def init_fn(params):
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        return AdaModState(
            count=jnp.zeros([], jnp.int32),
            exp_avg=zeros(),
            exp_avg_sq=zeros(),
            exp_avg_lr=zeros(),
        )

    def update_fn(updates, state, params):
        assert params is not None, "adamod requires params for weight decay"
        count = state.count + 1
        # Schedule indexed by the PRE-increment count: the first step trains
        # with schedule(0), matching the HF scheduler and the adam branch.
        lr = learning_rate(state.count) if callable(learning_rate) else learning_rate

        exp_avg = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, state.exp_avg, updates
        )
        exp_avg_sq = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, state.exp_avg_sq, updates
        )

        bias1 = 1 - b1 ** count.astype(jnp.float32)
        bias2 = 1 - b2 ** count.astype(jnp.float32)
        step_scale = lr * jnp.sqrt(bias2) / bias1

        def bounded_step(m, v, ema_lr, p, decays):
            denom = jnp.sqrt(v) + eps
            step_size = step_scale / denom
            new_ema_lr = beta3 * ema_lr + (1 - beta3) * step_size
            step_size = jnp.minimum(step_size, new_ema_lr)
            delta = -step_size * m
            if weight_decay != 0 and decays:
                delta = delta - weight_decay * lr * p
            return delta, new_ema_lr

        flat_m, treedef = jax.tree_util.tree_flatten(exp_avg)
        flat_v = treedef.flatten_up_to(exp_avg_sq)
        flat_e = treedef.flatten_up_to(state.exp_avg_lr)
        flat_p = treedef.flatten_up_to(params)
        flat_d = (
            treedef.flatten_up_to(decay_mask)
            if decay_mask is not None
            else [True] * len(flat_m)
        )

        deltas, new_emas = [], []
        for m, v, e, p, d_ in zip(flat_m, flat_v, flat_e, flat_p, flat_d):
            d, ne = bounded_step(m, v, e, p, d_)
            deltas.append(d)
            new_emas.append(ne)

        new_updates = jax.tree_util.tree_unflatten(treedef, deltas)
        new_ema_lr = jax.tree_util.tree_unflatten(treedef, new_emas)

        return new_updates, AdaModState(
            count=count, exp_avg=exp_avg, exp_avg_sq=exp_avg_sq, exp_avg_lr=new_ema_lr
        )

    tx = optax.GradientTransformation(init_fn, update_fn)
    if mask is not None:
        tx = optax.masked(tx, mask)
    return tx


def _scale_by_adam_no_bias_correction(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-6
) -> optax.GradientTransformation:
    """Adam moments WITHOUT bias correction — HF ``AdamW(correct_bias=False)``
    as the reference instantiates it (init.py:137)."""

    def init_fn(params):
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32), mu=zeros(), nu=zeros()
        )

    def update_fn(updates, state, params=None):
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, updates)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, updates
        )
        new_updates = jax.tree_util.tree_map(
            lambda m, v: m / (jnp.sqrt(v) + eps), mu, nu
        )
        return new_updates, optax.ScaleByAdamState(count=state.count + 1, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def _path_names(path) -> list:
    """Key names along one pytree path, as plain strings (DictKey /
    SequenceKey / attr entries normalized alike)."""
    return [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]


def param_path_mask(params, predicate) -> dict:
    """THE shared path walk of every per-parameter boolean mask: one
    boolean leaf per param leaf, ``predicate(names)`` over the leaf's path
    names. ``no_decay_mask`` and ``trainable_mask`` used to each walk the
    tree with their own path-string plumbing, which let the two masks
    disagree on how a new leaf's path reads (and therefore on its
    membership); deriving both from this single walk makes their tree
    structure identical by construction — which is also what lets them
    compose with the ZeRO-1 state plan (parallel/sharding.zero1_plan),
    itself keyed by the same path names."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: bool(predicate(_path_names(path))), params
    )


def no_decay_mask(params) -> dict:
    """True where weight decay applies — everything except biases,
    LayerNorm scales/biases (reference init.py:125-129 no_decay groups) and a
    linear-attention layer's decay parameters (``A_log``, ``dt_bias``)."""

    def decays(names):
        leaf_name = names[-1] if names else ""
        if leaf_name in ("bias", "A_log", "dt_bias"):
            return False
        if any("layer_norm" in n for n in names):
            return False
        return True

    return param_path_mask(params, decays)


def trainable_mask(params, trainer_params) -> Optional[dict]:
    """Fine-tune module selection (reference init.py:85-123): when
    ``finetune`` is set, only the flagged modules receive updates."""
    if not getattr(trainer_params, "finetune", False):
        return None

    wanted_roots = set()
    if getattr(trainer_params, "finetune_transformer", False):
        wanted_roots.add("transformer")
    if getattr(trainer_params, "finetune_position", False):
        wanted_roots.add("position_outputs")
    if getattr(trainer_params, "finetune_position_reg", False):
        wanted_roots.update(("reg_start", "reg_end"))
    if getattr(trainer_params, "finetune_class", False):
        wanted_roots.add("classifier")

    if not wanted_roots:
        raise AttributeError("Specify at least one module for fine-tuning.")

    return param_path_mask(
        params, lambda names: bool(names) and names[0] in wanted_roots
    )


OPTIMIZER_SHARDING_MODES = ("off", "zero1")


def parse_optimizer_sharding(spec, *, shard_optimizer=None) -> str:
    """Flag domain of ``--optimizer_sharding``: ``off`` (replicate the full
    optimizer state per chip — the historical layout) or ``zero1`` (shard
    every state leaf over the mesh ``data`` axis and run the weight update
    on each replica's shard only). ``None`` defers to the legacy
    ``--shard_optimizer`` boolean so existing configs keep working."""
    if spec is None:
        return "zero1" if shard_optimizer else "off"
    mode = str(spec).strip().lower()
    if mode in ("", "none", "false", "0"):
        return "off"
    if mode in ("true", "1", "on"):
        return "zero1"
    if mode not in OPTIMIZER_SHARDING_MODES:
        raise ValueError(
            f"bad optimizer_sharding {spec!r} (choose from "
            f"{'|'.join(OPTIMIZER_SHARDING_MODES)})"
        )
    return mode


def build_optimizer(
    trainer_params,
    params,
    *,
    num_training_steps: int,
    max_grad_norm: Optional[float] = None,
    warmup_coef: Optional[float] = None,
    optimizer_sharding: Optional[str] = None,
) -> tuple:
    """Optimizer selection + schedule (reference init.py:134-145 +
    trainer.py:116-126 + clip trainer.py:221-225 fused into one chain).

    Returns ``(optax transform, schedule_fn, schedule_count_fn)``.
    ``schedule_count_fn(opt_state)`` reads the schedule step count out of the
    transform's own state, structurally — built here, where the chain layout
    is decided, so no caller ever scans the state tree by leaf name. The
    count only advances on APPLIED updates, which is what makes it the right
    schedule index under loss scaling (overflow steps freeze the whole
    state, count included). ``warmup_coef``, when given, overrides
    ``trainer_params.warmup_coef`` (the Trainer field is the single source
    of truth when built through the Trainer).

    ``optimizer_sharding`` (``off``/``zero1``; ``None`` defers to
    ``trainer_params.optimizer_sharding`` / the legacy ``shard_optimizer``
    boolean) is validated HERE — the chain's transforms are layout-agnostic
    (elementwise over whatever leaves they are given), so the actual state
    placement and the reduce-scatter/all-gather update pattern are applied
    where the state is materialized: ``Trainer.init_opt_state`` and the
    jitted train step. A bad mode must still fail at build time, not at the
    first step.
    """
    parse_optimizer_sharding(
        optimizer_sharding
        if optimizer_sharding is not None
        else getattr(trainer_params, "optimizer_sharding", None),
        shard_optimizer=getattr(trainer_params, "shard_optimizer", False),
    )
    if warmup_coef is None:
        warmup_coef = getattr(trainer_params, "warmup_coef", 0.0)
    lr = trainer_params.lr

    if warmup_coef and warmup_coef > 0:
        num_warmup = int(num_training_steps * warmup_coef)
        schedule = linear_warmup_schedule(lr, num_warmup, num_training_steps)
    else:
        schedule = lambda step: jnp.asarray(lr, jnp.float32)

    decay_mask = no_decay_mask(params)

    if getattr(trainer_params, "optimizer", "adam") == "adam":
        # HF AdamW(correct_bias=False): no bias correction on the moments.
        core = optax.chain(
            _scale_by_adam_no_bias_correction(b1=0.9, b2=0.999, eps=1e-6),
            optax.add_decayed_weights(trainer_params.weight_decay, mask=decay_mask),
            optax.scale_by_learning_rate(schedule),
        )
    else:
        core = adamod(
            schedule,
            weight_decay=trainer_params.weight_decay,
            decay_mask=decay_mask,
        )

    is_adam = getattr(trainer_params, "optimizer", "adam") == "adam"
    has_clip = max_grad_norm is not None and max_grad_norm > 0

    chain = [core]
    if has_clip:
        chain.insert(0, optax.clip_by_global_norm(max_grad_norm))

    tx = optax.chain(*chain)

    tmask = trainable_mask(params, trainer_params)

    def schedule_count_fn(opt_state):
        s = opt_state
        if tmask is not None:
            s = s[0].inner_state  # masked(tx) wrapper, chain slot 0
        s = s[1] if has_clip else s[0]  # `core`'s slot in the outer chain
        if is_adam:
            s = s[0]  # core = chain(adam_moments, decay, lr)
        return s.count  # ScaleByAdamState / AdaModState
    if tmask is not None:
        # optax.masked passes NON-masked updates through UNCHANGED — i.e. the
        # frozen leaves would come out as their raw gradients and be added to
        # the params. Chain a set_to_zero over the frozen complement so
        # frozen modules stay frozen (reference semantics: frozen params are
        # simply never given to the optimizer, init.py:85-123).
        frozen = jax.tree_util.tree_map(lambda m: not m, tmask)
        tx = optax.chain(
            optax.masked(tx, tmask), optax.masked(optax.set_to_zero(), frozen)
        )

    return tx, schedule, schedule_count_fn

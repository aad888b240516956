"""Loss zoo — pure-JAX, jit-safe.

Parity targets (reference ``modules/model/model/loss.py`` semantics, checked
numerically against torch in tests):

- ``cross_entropy_with_ignore``: ``nn.CrossEntropyLoss(ignore_index=-1)`` as
  used for span start/end heads (init.py:34-35) — mean over non-ignored rows;
  optional per-class weights reproduce ``CrossEntropyLoss(weight=...)``
  (init.py:23) including its weighted-mean denominator.
- ``label_smoothing_loss``: ``LabelSmoothingLossWithLogits`` (loss.py:5-38) —
  KLDiv-batchmean against the smoothed target distribution when smoothing>0
  (smoothing mass split over ``n_classes - num_ignore``), NLL otherwise.
- ``binary_focal_loss``: ``BinaryFocalLossWithLogits`` (loss.py:41-54).
- ``focal_loss``: ``FocalLossWithLogits`` (loss.py:57-71) — focal reweighting
  applied *inside* the NLL pick, with ignore-index masking.
- ``mse_loss``: ``nn.MSELoss`` for the position regressors (init.py:36-37).
- ``WeightedLoss``: the per-head aggregator (loss.py:74-106). Functional
  twist: instead of mutating AverageMeters inside the loss (impossible under
  jit), ``__call__`` returns ``(total, per_head_values)`` and the trainer
  feeds meters host-side.

All losses take f32 logits (the model promotes) and integer/float targets.

Every loss is a sum over rows divided by a normaliser that the TARGETS alone
decide (a count of valid rows, a sum of class weights, a row count). A
data-parallel chip that holds only its own rows (the trainer's once-a-step
gradient exchange) brings the normaliser of the whole micro-batch as
``denom``; its value is then the chip's share, and the chips' values and
gradients add up to the whole batch's. ``loss_denominator`` computes that
normaliser; with ``denom=None`` each function is its historical arithmetic.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _log_softmax(logits):
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def _normaliser(w, floor):
    """A weighted mean's divisor: the weights' sum, kept off zero."""
    return jnp.maximum(jnp.sum(w), floor)


def _weighted_mean(x, w, floor, denom=None):
    """``sum(x * w)`` over the normaliser of ``w``, or over ``denom`` where
    the caller brings the whole micro-batch's."""
    total = jnp.sum(x * w)
    return total / (_normaliser(w, floor) if denom is None else denom)


def _count(x):
    """``jnp.mean``'s divisor: every element counts."""
    return jnp.float32(x.size)


def _mean(x, denom=None):
    """``jnp.mean(x)`` (and lowered as it is), or ``x``'s sum over ``denom``."""
    return jnp.sum(x) / (_count(x) if denom is None else denom)


def _ce_weights(valid, safe_targets, class_weights):
    """Per-row weight of the cross-entropy mean, and the floor of their sum."""
    if class_weights is not None:
        return class_weights[safe_targets] * valid, 1e-12
    return valid.astype(jnp.float32), 1.0


def cross_entropy_with_ignore(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    ignore_index: int = -1,
    class_weights: Optional[jnp.ndarray] = None,
    denom: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mean NLL over rows whose target != ignore_index.

    With ``class_weights`` the mean is weighted by the target's class weight
    (torch ``CrossEntropyLoss(weight=...)`` denominator semantics).
    """
    log_probs = _log_softmax(logits)
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)

    nll = -jnp.take_along_axis(log_probs, safe_targets[..., None], axis=-1)[..., 0]

    w, floor = _ce_weights(valid, safe_targets, class_weights)
    return _weighted_mean(nll, w, floor, denom)


def _ce_denominator(targets, *, ignore_index: int = -1, class_weights=None,
                    **_):
    valid = targets != ignore_index
    return _normaliser(
        *_ce_weights(valid, jnp.where(valid, targets, 0), class_weights))


def label_smoothing_loss(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    n_classes: int,
    smoothing: float = 0.0,
    ignore_index: int = -100,
    valid: Optional[jnp.ndarray] = None,
    denom: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``valid`` (optional bool [N]) restricts the mean to those rows — the
    packed-segment path (KLDiv batchmean has no ignore_index of its own, so
    absent segments must be masked out of the mean explicitly). ``None``
    keeps the historical whole-batch arithmetic bit-exactly."""
    assert 0 <= smoothing <= 1
    log_probs = _log_softmax(logits)

    if smoothing <= 0:
        if valid is not None:
            targets = jnp.where(valid, targets, ignore_index)
        return cross_entropy_with_ignore(
            logits, targets, ignore_index=ignore_index, denom=denom)

    num_ignore = 1 + (0 <= ignore_index < n_classes)
    fill_value = smoothing / (n_classes - num_ignore)
    confidence = 1.0 - smoothing

    safe_targets = targets if valid is None else jnp.where(valid, targets, 0)
    target_dist = jnp.full((targets.shape[0], n_classes), fill_value, dtype=jnp.float32)
    target_dist = jnp.asarray(target_dist).at[
        jnp.arange(targets.shape[0]), safe_targets
    ].set(confidence)
    if 0 <= ignore_index < n_classes:
        target_dist = target_dist.at[:, ignore_index].set(0.0)

    # KLDivLoss(reduction='batchmean'): sum over classes of t*(log t - log p),
    # averaged over the batch; 0*log(0) := 0.
    t_log_t = jnp.where(target_dist > 0, target_dist * jnp.log(target_dist), 0.0)
    kl = jnp.sum(t_log_t - target_dist * log_probs, axis=-1)
    if valid is None:
        return _mean(kl, denom)
    return _weighted_mean(kl, valid.astype(jnp.float32), 1.0, denom)


def _smoothing_denominator(targets, *, smoothing: float = 0.0,
                           ignore_index: int = -100, valid=None, **_):
    if smoothing <= 0:
        if valid is not None:
            targets = jnp.where(valid, targets, ignore_index)
        return _ce_denominator(targets, ignore_index=ignore_index)
    if valid is None:
        return _count(targets)
    return _normaliser(valid.astype(jnp.float32), 1.0)


def binary_focal_loss(
    logits: jnp.ndarray, targets: jnp.ndarray, *, alpha: float = 1.0,
    gamma: float = 2.0, denom: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    # stable BCE-with-logits
    bce = jnp.maximum(logits, 0) - logits * targets + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    probs = jnp.exp(-bce)
    return _mean(alpha * (1 - probs) ** gamma * bce, denom)


def focal_loss(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    alpha: float = 1.0,
    gamma: float = 2.0,
    ignore_index: int = -1,
    denom: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    log_probs = _log_softmax(logits)
    probs = jnp.exp(log_probs)
    weighted = alpha * (1 - probs) ** gamma * log_probs

    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    picked = -jnp.take_along_axis(weighted, safe_targets[..., None], axis=-1)[..., 0]

    return _weighted_mean(picked, valid.astype(jnp.float32), 1.0, denom)


def mse_loss(preds: jnp.ndarray, targets: jnp.ndarray, *,
             denom: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    return _mean(
        (preds.astype(jnp.float32) - targets.astype(jnp.float32)) ** 2, denom)


def masked_mse_loss(preds: jnp.ndarray, targets: jnp.ndarray,
                    valid: jnp.ndarray, *,
                    denom: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``mse_loss`` over rows where ``valid`` only (packed-segment variant:
    absent segments carry zero predictions/targets that must not dilute the
    mean)."""
    v = valid.astype(jnp.float32)
    sq = (preds.astype(jnp.float32) - targets.astype(jnp.float32)) ** 2
    return _weighted_mean(sq, v, 1.0, denom)


def _count_denominator(targets, **_):
    return _count(targets)


def _masked_denominator(targets, *, valid, **_):
    return _normaliser(valid.astype(jnp.float32), 1.0)


# what each loss divides its sum over rows by, from the targets alone
_DENOMINATOR = {
    cross_entropy_with_ignore: _ce_denominator,
    label_smoothing_loss: _smoothing_denominator,
    binary_focal_loss: _count_denominator,
    focal_loss: _ce_denominator,
    mse_loss: _count_denominator,
    masked_mse_loss: _masked_denominator,
}


def loss_denominator(loss_f: Callable, targets, **kwargs) -> jnp.ndarray:
    """The scalar that ``loss_f(preds, targets, **kwargs)`` divides its sum
    over rows by. ``loss_f`` is one of this module's losses, bare or under
    ``functools.partial``."""
    if isinstance(loss_f, functools.partial):
        kwargs = {**loss_f.keywords, **kwargs}
        loss_f = loss_f.func
    return _DENOMINATOR[loss_f](targets, **kwargs)


class WeightedLoss:
    """Weighted sum of per-head losses (reference loss.py:74-106).

    ``losses`` maps head name -> (loss_fn, weight). ``__call__`` returns
    ``(total_loss, {head: value})``; per-head values are the *unweighted*
    losses, matching what the reference logged into its meters.

    ``denominators(targets)`` gives every head's normaliser from the targets
    alone; handed back to ``__call__`` with the rows of one data-parallel
    chip, the total and the values are that chip's share of the batch's
    (module docstring).
    """

    def __init__(self, losses: Dict[str, Tuple[Callable, float]]):
        self._losses = losses

    @property
    def keys(self):
        return self._losses.keys()

    def value_structure(self) -> dict:
        """Zero-valued dict with the shape of ``__call__``'s values output —
        used as the scan carry init for in-step gradient accumulation."""
        out = {key: 0.0 for key in self._losses}
        out["loss"] = 0.0
        return out

    def _terms(self, targets: dict):
        """``(head, weight, loss function, its targets, its keywords)``, a
        head at a time: what ``__call__`` evaluates and ``denominators``
        normalises."""
        for key, (loss_f, weight) in self._losses.items():
            yield key, weight, loss_f, targets[key], {}

    def _head_preds(self, preds: dict, key: str):
        return preds[key]

    def denominators(self, targets: dict) -> dict:
        return {
            key: loss_denominator(loss_f, t, **kw)
            for key, _, loss_f, t, kw in self._terms(targets)
        }

    def __call__(self, preds: dict, targets: dict,
                 denominators: Optional[dict] = None) -> Tuple[jnp.ndarray, dict]:
        assert set(preds.keys()) >= set(self._losses.keys())
        assert set(targets.keys()) >= set(self._losses.keys())

        values = {}
        full_loss = 0.0
        for key, weight, loss_f, t, kw in self._terms(targets):
            if denominators is not None:
                kw = {**kw, "denom": denominators[key]}
            loss = loss_f(self._head_preds(preds, key), t, **kw)
            values[key] = loss
            full_loss = full_loss + weight * loss

        values["loss"] = full_loss
        return full_loss, values


def _flat_segments(x):
    """``[R, S, ...]`` -> ``[R*S, ...]``."""
    x = jnp.asarray(x)
    return x.reshape((-1,) + x.shape[2:])


class PackedWeightedLoss(WeightedLoss):
    """``WeightedLoss`` adapter for sequence-packed batches.

    Predictions arrive per SEGMENT (``[R, S, ...]`` — the packed QAModel's
    head outputs) and targets carry a ``segment_mask`` validity plane
    (data/packing.collate_packed). Every head is computed over the
    flattened ``R*S`` segment axis with absent segments excluded: the span
    and class heads reuse the base loss functions verbatim by rewriting
    absent segments' targets to the head's ignore_index (span CE already
    ignores -1, class CE -100, focal -1); mse and smoothing>0 — which have
    no ignore semantics — go through the masked variants above. Returned
    values are means over REAL segments (= original examples), so the
    trainer's row-weighted epoch meters stay per-example-correct when
    weighted by the batch's real segment count.
    """

    def __init__(self, base: WeightedLoss):
        super().__init__(base._losses)
        self._cls_fns = {}
        for key, (fn, _weight) in base._losses.items():
            if key in ("start_class", "end_class", "start_reg", "end_reg"):
                continue
            partial = isinstance(fn, functools.partial)
            base_fn = fn.func if partial else fn
            kw = dict(fn.keywords) if partial else {}
            if base_fn is label_smoothing_loss:
                self._cls_fns[key] = ("smooth", kw)
            elif base_fn is cross_entropy_with_ignore:
                self._cls_fns[key] = ("ignore", kw.get("ignore_index", -1))
            elif base_fn is focal_loss:
                self._cls_fns[key] = ("ignore", kw.get("ignore_index", -1))
            else:
                raise NotImplementedError(
                    f"PackedWeightedLoss cannot adapt head {key!r} "
                    f"({base_fn}): no ignore/mask semantics known"
                )

    def _terms(self, targets: dict):
        valid = targets["segment_mask"].reshape(-1) > 0
        for key, (loss_f, weight) in self._losses.items():
            t = _flat_segments(targets[key])
            if key in ("start_class", "end_class"):
                # span CE ignores -1 — absent segments carry -1 already
                # (collate) but pad ROWS repeat real labels, so re-mask
                yield key, weight, loss_f, jnp.where(valid, t, -1), {}
            elif key in ("start_reg", "end_reg"):
                yield key, weight, masked_mse_loss, t, {"valid": valid}
            else:
                kind, arg = self._cls_fns[key]
                if kind == "smooth":
                    yield (key, weight, label_smoothing_loss, t,
                           {"valid": valid, **arg})
                else:
                    yield key, weight, loss_f, jnp.where(valid, t, arg), {}

    def _head_preds(self, preds: dict, key: str):
        return _flat_segments(preds[key])


def build_loss(params, train_weights: Optional[dict] = None) -> WeightedLoss:
    """Select the classification loss + per-head weights (init.py:18-40)."""
    label_weights = None
    if train_weights is not None and train_weights.get("label_weights") is not None:
        label_weights = jnp.asarray(train_weights["label_weights"], dtype=jnp.float32)

    n_classes = 5
    if params.loss == "ce":
        class_loss = functools.partial(
            cross_entropy_with_ignore, ignore_index=-100, class_weights=label_weights
        )
    elif params.loss == "focal":
        # reference FocalLossWithLogits defaults to ignore_index=-1 (loss.py:59)
        class_loss = functools.partial(
            focal_loss, alpha=params.focal_alpha, gamma=params.focal_gamma,
            ignore_index=-1,
        )
    elif params.loss == "smooth":
        class_loss = functools.partial(
            label_smoothing_loss, n_classes=n_classes, smoothing=params.smooth_alpha
        )
    else:
        raise NotImplementedError(f"Unknown loss {params.loss}")

    def _wght(name):
        return getattr(params, name, 1)

    span_ce = functools.partial(cross_entropy_with_ignore, ignore_index=-1)

    return WeightedLoss(
        {
            "start_class": (span_ce, _wght("w_start")),
            "end_class": (span_ce, _wght("w_end")),
            "start_reg": (mse_loss, _wght("w_start_reg")),
            "end_reg": (mse_loss, _wght("w_end_reg")),
            "cls": (class_loss, _wght("w_cls")),
        }
    )

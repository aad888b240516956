"""Loss/metric tests: numerical parity vs torch and sklearn where available."""

import numpy as np
import pytest

import jax.numpy as jnp

from ml_recipe_tpu.losses import (
    WeightedLoss,
    binary_focal_loss,
    build_loss,
    cross_entropy_with_ignore,
    focal_loss,
    label_smoothing_loss,
    mse_loss,
)
from ml_recipe_tpu.metrics import (
    AverageMeter,
    MAPMeter,
    accuracy_score,
    average_precision,
)

# no-jit / tiny-jit module: part of the <2 min unit tier (VERDICT r2 #7)
pytestmark = pytest.mark.unit

torch = pytest.importorskip("torch")


def _rand_logits(B=8, C=5, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(B, C)).astype(np.float32)


def test_cross_entropy_matches_torch():
    logits = _rand_logits()
    targets = np.array([0, 1, 2, 3, 4, -1, 2, -1])
    ours = cross_entropy_with_ignore(jnp.asarray(logits), jnp.asarray(targets))
    ref = torch.nn.CrossEntropyLoss(ignore_index=-1)(
        torch.tensor(logits), torch.tensor(targets, dtype=torch.long)
    )
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_cross_entropy_class_weights_matches_torch():
    logits = _rand_logits()
    targets = np.array([0, 1, 2, 3, 4, 0, 2, 1])
    w = np.array([0.1, 0.2, 0.3, 0.25, 0.15], dtype=np.float32)
    ours = cross_entropy_with_ignore(
        jnp.asarray(logits), jnp.asarray(targets), ignore_index=-100,
        class_weights=jnp.asarray(w),
    )
    ref = torch.nn.CrossEntropyLoss(weight=torch.tensor(w))(
        torch.tensor(logits), torch.tensor(targets, dtype=torch.long)
    )
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_label_smoothing_matches_torch_kldiv():
    """Reproduce the reference LabelSmoothingLossWithLogits computation."""
    logits = _rand_logits()
    targets = np.array([0, 1, 2, 3, 4, 0, 2, 1])
    n_classes, smoothing, ignore_index = 5, 0.1, -100

    ours = label_smoothing_loss(
        jnp.asarray(logits), jnp.asarray(targets),
        n_classes=n_classes, smoothing=smoothing, ignore_index=ignore_index,
    )

    log_probs = torch.log_softmax(torch.tensor(logits), dim=-1)
    fill = smoothing / (n_classes - 1)
    dist = torch.full((8, n_classes), fill)
    dist.scatter_(-1, torch.tensor(targets, dtype=torch.long).unsqueeze(-1), 1 - smoothing)
    ref = torch.nn.KLDivLoss(reduction="batchmean")(log_probs, dist)
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_label_smoothing_zero_falls_back_to_nll():
    logits = _rand_logits()
    targets = np.array([0, 1, 2, 3, 4, 0, 2, 1])
    ours = label_smoothing_loss(
        jnp.asarray(logits), jnp.asarray(targets), n_classes=5, smoothing=0.0
    )
    ref = torch.nn.NLLLoss()(
        torch.log_softmax(torch.tensor(logits), dim=-1),
        torch.tensor(targets, dtype=torch.long),
    )
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_binary_focal_matches_torch():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(16,)).astype(np.float32)
    targets = (rng.random(16) > 0.5).astype(np.float32)
    alpha, gamma = 1.0, 2.0

    ours = binary_focal_loss(jnp.asarray(logits), jnp.asarray(targets),
                             alpha=alpha, gamma=gamma)

    bce = torch.nn.BCEWithLogitsLoss(reduction="none")(
        torch.tensor(logits), torch.tensor(targets)
    )
    probs = torch.exp(-bce)
    ref = torch.mean(alpha * (1 - probs) ** gamma * bce)
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_focal_matches_torch():
    logits = _rand_logits()
    targets = np.array([0, 1, 2, 3, 4, -1, 2, 1])
    alpha, gamma = 1.0, 2.0

    ours = focal_loss(jnp.asarray(logits), jnp.asarray(targets), alpha=alpha, gamma=gamma)

    log_probs = torch.log_softmax(torch.tensor(logits), dim=-1)
    probs = torch.exp(log_probs)
    ref = torch.nn.NLLLoss(ignore_index=-1)(
        alpha * (1 - probs) ** gamma * log_probs, torch.tensor(targets, dtype=torch.long)
    )
    np.testing.assert_allclose(float(ours), float(ref), rtol=5e-5)


def test_mse():
    a = jnp.asarray([1.0, 2.0]); b = jnp.asarray([0.0, 0.0])
    np.testing.assert_allclose(float(mse_loss(a, b)), 2.5)


def test_weighted_loss_aggregation():
    class P:
        loss = "smooth"; smooth_alpha = 0.01
        w_start = 1; w_end = 1; w_start_reg = 0.5; w_end_reg = 0.5; w_cls = 2
        focal_alpha = 1; focal_gamma = 2

    wl = build_loss(P())
    B, L = 4, 12
    rng = np.random.default_rng(0)
    preds = {
        "start_class": jnp.asarray(rng.normal(size=(B, L)).astype(np.float32)),
        "end_class": jnp.asarray(rng.normal(size=(B, L)).astype(np.float32)),
        "start_reg": jnp.asarray(rng.random(B).astype(np.float32)),
        "end_reg": jnp.asarray(rng.random(B).astype(np.float32)),
        "cls": jnp.asarray(rng.normal(size=(B, 5)).astype(np.float32)),
    }
    targets = {
        "start_class": jnp.asarray([1, -1, 3, 0]),
        "end_class": jnp.asarray([2, -1, 5, 1]),
        "start_reg": jnp.asarray(rng.random(B).astype(np.float32)),
        "end_reg": jnp.asarray(rng.random(B).astype(np.float32)),
        "cls": jnp.asarray([0, 4, 2, 1]),
    }
    total, values = wl(preds, targets)
    manual = (
        values["start_class"] + values["end_class"]
        + 0.5 * values["start_reg"] + 0.5 * values["end_reg"]
        + 2 * values["cls"]
    )
    np.testing.assert_allclose(float(total), float(manual), rtol=1e-6)
    assert float(values["loss"]) == float(total)


def test_build_loss_variants():
    for loss_name in ("ce", "focal", "smooth"):
        class P:
            loss = loss_name; smooth_alpha = 0.01
            focal_alpha = 1; focal_gamma = 2
            w_start = w_end = w_cls = 1; w_start_reg = w_end_reg = 0

        wl = build_loss(P())
        assert set(wl.keys) == {"start_class", "end_class", "start_reg", "end_reg", "cls"}


# -- metrics ------------------------------------------------------------------


def test_average_meter():
    m = AverageMeter()
    for v in [1.0, 2.0, 3.0]:
        m.update(v)
    assert m() == 2.0


def test_average_meter_weighted():
    """Weighted updates make the running mean per-SAMPLE-correct when batch
    means cover unequal row counts (bucketed batches, trimmed eval tails)."""
    m = AverageMeter()
    m.update(1.0, 8)
    m.update(5.0, 2)
    assert m() == pytest.approx((8 * 1.0 + 2 * 5.0) / 10)
    # zero/negative weights are ignored, not divide-by-zero
    m2 = AverageMeter()
    m2.update(3.0, 0)
    assert m2() == 0.0 and m2._counter == 0


def test_accuracy():
    assert accuracy_score([1, 2, 3], [1, 2, 0]) == pytest.approx(2 / 3)


def test_average_precision_matches_sklearn():
    sklearn_metrics = pytest.importorskip("sklearn.metrics")
    rng = np.random.default_rng(0)
    for trial in range(5):
        y_true = (rng.random(50) > 0.7).astype(int)
        y_score = rng.random(50)
        if y_true.sum() == 0:
            continue
        ours = average_precision(y_true, y_score)
        ref = sklearn_metrics.average_precision_score(y_true, y_score)
        np.testing.assert_allclose(ours, ref, rtol=1e-9)


def test_average_precision_no_positives_nan():
    assert np.isnan(average_precision([0, 0], [0.3, 0.4]))


def test_map_meter():
    rng = np.random.default_rng(0)
    m = MAPMeter()
    probas = rng.random((20, 3))
    labels = rng.integers(0, 3, 20)
    m.update(["a", "b", "c"], probas, labels)
    out = m()
    assert set(out.keys()) == {"a", "b", "c", "map"}
    assert 0 <= out["map"] <= 1


# -- denominators: what a data-parallel chip needs of the rows it lacks --------

def _denominator_cases():
    import functools

    from ml_recipe_tpu.losses.losses import masked_mse_loss

    rng = np.random.default_rng(3)
    n, c = 12, 5
    logits = rng.normal(size=(n, c)).astype(np.float32)
    cls = rng.integers(0, c, n).astype(np.int32)
    ignored = cls.copy()
    ignored[[0, 1, 2, 7]] = -1          # uneven over any split of the rows
    weights = jnp.asarray([0.2, 1.0, 3.0, 0.5, 2.0], jnp.float32)
    reg = rng.normal(size=n).astype(np.float32)
    valid = np.arange(n) % 3 != 0
    return {
        "ce_ignore": (functools.partial(
            cross_entropy_with_ignore, ignore_index=-1), logits, ignored, {}),
        "ce_weighted": (functools.partial(
            cross_entropy_with_ignore, ignore_index=-1,
            class_weights=weights), logits, ignored, {}),
        "smooth": (functools.partial(
            label_smoothing_loss, n_classes=c, smoothing=0.1),
            logits, cls, {}),
        "smooth_valid": (functools.partial(
            label_smoothing_loss, n_classes=c, smoothing=0.1),
            logits, cls, {"valid": valid}),
        "smooth_zero": (functools.partial(
            label_smoothing_loss, n_classes=c, smoothing=0.0,
            ignore_index=-1), logits, ignored, {}),
        "focal": (functools.partial(focal_loss, alpha=0.5, gamma=2.0,
                                    ignore_index=-1), logits, ignored, {}),
        "binary_focal": (binary_focal_loss, logits[:, 0],
                         (cls > 2).astype(np.float32), {}),
        "mse": (mse_loss, reg, reg[::-1].copy(), {}),
        "masked_mse": (masked_mse_loss, reg, reg[::-1].copy(),
                       {"valid": valid}),
    }


@pytest.mark.parametrize("name", sorted(_denominator_cases()))
def test_chip_shares_add_up_to_the_whole_batch_loss(name):
    """``loss_denominator`` is the normaliser from the targets alone, and a
    loss given it as ``denom`` on a part of the rows returns that part's
    SHARE: over any split of the rows the shares add up to the loss of the
    whole batch (what the trainer's once-a-step exchange rests on)."""
    from ml_recipe_tpu.losses.losses import loss_denominator

    loss_f, preds, targets, kw = _denominator_cases()[name]
    whole = float(loss_f(preds, targets, **kw))
    denom = loss_denominator(loss_f, targets, **kw)
    np.testing.assert_allclose(
        float(loss_f(preds, targets, denom=denom, **kw)), whole, rtol=1e-6)
    shares = 0.0
    for rows in (slice(0, 3), slice(3, 4), slice(4, 12)):   # uneven chips
        part = {k: v[rows] for k, v in kw.items()}
        shares += float(loss_f(preds[rows], targets[rows], denom=denom,
                               **part))
    np.testing.assert_allclose(shares, whole, rtol=1e-5)
    if name in ("ce_ignore", "ce_weighted", "focal"):
        # and the per-chip mean, DDP's average of averages, is not it
        means = [float(loss_f(preds[r], targets[r],
                              **{k: v[r] for k, v in kw.items()}))
                 for r in (slice(0, 6), slice(6, 12))]
        assert abs(np.mean(means) - whole) > 1e-3


def test_weighted_loss_denominators_cover_every_head():
    from ml_recipe_tpu.losses import PackedWeightedLoss

    class P:
        loss = "smooth"
        smooth_alpha = 0.1
        w_start = w_end = w_cls = 1
        w_start_reg = w_end_reg = 0.5

    loss = build_loss(P())
    rng = np.random.default_rng(0)
    n, L = 6, 16
    preds = {"start_class": rng.normal(size=(n, L)).astype(np.float32),
             "end_class": rng.normal(size=(n, L)).astype(np.float32),
             "start_reg": rng.normal(size=n).astype(np.float32),
             "end_reg": rng.normal(size=n).astype(np.float32),
             "cls": rng.normal(size=(n, 5)).astype(np.float32)}
    targets = {"start_class": np.array([1, -1, 3, -1, -1, 2], np.int32),
               "end_class": np.array([2, -1, 4, 5, -1, 3], np.int32),
               "start_reg": rng.normal(size=n).astype(np.float32),
               "end_reg": rng.normal(size=n).astype(np.float32),
               "cls": rng.integers(0, 5, n).astype(np.int32)}
    dens = loss.denominators(targets)
    assert set(dens) == set(loss.keys)
    assert float(dens["start_class"]) == 3 and float(dens["end_class"]) == 4
    total, values = loss(preds, targets)
    shares = [loss({k: v[r] for k, v in preds.items()},
                   {k: v[r] for k, v in targets.items()}, dens)
              for r in (slice(0, 1), slice(1, 6))]
    np.testing.assert_allclose(
        float(shares[0][0] + shares[1][0]), float(total), rtol=1e-5)
    for key in values:
        np.testing.assert_allclose(
            float(shares[0][1][key] + shares[1][1][key]), float(values[key]),
            rtol=1e-5, err_msg=key)

    # packed: per-segment labels under a segment mask, same contract
    packed = PackedWeightedLoss(loss)
    S = 2
    seg = lambda x: np.reshape(x, (n // S, S) + x.shape[1:])  # noqa: E731
    p_preds = {k: seg(v) for k, v in preds.items()}
    p_targets = {k: seg(v) for k, v in targets.items()}
    p_targets["segment_mask"] = np.array([[1, 1], [1, 0], [0, 0]], np.int32)
    p_dens = packed.denominators(p_targets)
    assert float(p_dens["start_reg"]) == 3 and float(p_dens["cls"]) == 3
    p_total, _ = packed(p_preds, p_targets)
    p_shares = [packed({k: v[r] for k, v in p_preds.items()},
                       {k: v[r] for k, v in p_targets.items()}, p_dens)[0]
                for r in (slice(0, 1), slice(1, 3))]
    np.testing.assert_allclose(float(sum(p_shares)), float(p_total),
                               rtol=1e-5)

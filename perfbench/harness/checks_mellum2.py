"""The comparison that decides ``correct`` for the ``mellum`` trunk: the
system (bf16 matmuls, the timed window and causal kernels) against
``reference_mellum2`` (float32, highest precision, an explicit band mask) on
the runner's ragged seeded rows at the job's sequence length. Each row is
judged AS A BATCH OF ONE, the shape the cell trains (one row a micro-batch),
so what the layers sow (q, k and v of four attention layers) stays a quarter
of what four rows at once would hold beside the trainer's state. Top-8 of 64
is a discontinuous function of the hidden state, so the reference is run with
the experts the system chose (``routing=``) and the routing is judged apart,
as ``checks_joyai`` and ``checks_lfm2`` do; the attention operators are
judged apart too, because a logit at seeded weights cannot tell a window of
1,024 from 8,192 keys averaged, nor YaRN's frequencies from the plain ones:

(a) **the router alone**, layer by layer, on the state the system's router
    read (sown next to its choice): wherever the reference's margin (8th less
    9th router LOGIT) exceeds ``ROUTING_MARGIN`` the chosen sets must be
    equal, on every real token. Two f32 evaluations of one dot product of
    2,304 terms differ by about 1e-6; a router computed in bf16 flips tokens
    at margins up to several 1e-3.
(b) **the routing along the reference's trajectory**: the share of a layer's
    real tokens whose chosen set differs from what the reference itself
    chooses on ITS float32 state (every layer before routed as the system
    routed) may not pass ``TRAJECTORY_DIFFER_SHARE``.
(c) **logits and loss**, the reference routed as the system routed: the form
    of ``checks.logit_tolerances``, ``c x 2^-7 x sqrt(layers) x |w|_2``, with
    the ``c`` that ``ROUNDING_FACTOR`` gives; ``LOSS_RTOL`` as BERT's.
(d) **each kind of attention layer's operator alone**, attention layer by
    layer, on the q, k and v the system's core read (sown after norm and
    rotation, next to what it wrote) against the reference's core on the
    same inputs under the explicit band of the layer's kind: the RMS of the
    difference over real tokens, as a share of the RMS of the reference's
    output there, may not pass ``CORE_ERROR_SHARE``. The kernels keep the
    logits, the running maximum and sum in f32 and round the probabilities
    once, for the second product, and the output once. A causal mask in a
    sliding layer (the window left out) or a softmax computed in bf16 land
    outside it.
(e) **what the cores read, along the reference's trajectory**: q, k and v as
    the system's layers made them against the reference's own on ITS float32
    state: the mean distance over real tokens, as a share of the reference's
    RMS, may not pass ``INPUT_DRIFT``. Part (d) hands the reference the
    rotated q and k the system made, so it cannot see a wrong rotation: the
    sliding layers' rotation used in the full-attention layer (no YaRN: other
    frequencies from pair 18 on and no factor of 1.277) reads here.

The judged forward is compiled with ``xla_allow_excess_precision`` off, so
that an operator is judged on the bf16 inputs it has sown and not on their
f32 values. ``compare`` names the parts that failed (``failed_parts``). The
readings behind the limits are in PERF.md (section 4);
``scripts/mellum2_tolerance_readings.py`` takes them by handing ``compare`` a
lowered system (``system=``): the window left out, no YaRN, a bf16 softmax, a
bf16 router, float8 matmul inputs, partial sums kept in bf16.
"""

from __future__ import annotations

import numpy as np

from . import checks, reference_mellum2
from .checks_joyai import ROUTING, ROUTING_MARGIN, expert_layers, routing_report
# one layer's inputs against the reference's own: the mean distance on real
# tokens over the reference's RMS there, input by input (here q, k and v)
from .checks_olmo_hybrid import input_drift

# Readings on the chip at the published widths, the embedding at unit RMS (my
# chip runs, PR 37; the system on seeds 3700000501, 3700000511, 2147484512,
# 3700000514-517; the controls on 3700000501,
# scripts/mellum2_tolerance_readings.py):
# (a) the system 0 tokens above the margin in every layer; a bf16 router
# 438-493 of about 18,780 a layer, bf16 partial sums 818-845.
# (b) the system 1.8-2.2% of layer 0's real tokens, 2.5-3.0%, 3.0-3.6% and
# 3.7-3.9% of the next three's (a deeper state holds more roundings); partial
# sums kept in bf16 4.7 / 4.7 / 4.8 / 5.4%; float8 matmul inputs 94-97%.
TRAJECTORY_DIFFER_SHARE = 0.045
# (c) the system 0.024-0.033 (span) and 0.010-0.017 (cls) against limits of
# 0.093-0.095; float8 matmul inputs 1.5-1.6 and 0.36; the window left out
# 0.56-0.73, no YaRN 0.34-0.41. The loss: 0.00004-0.0005 relative against 1e-2.
ROUNDING_FACTOR = 3.0       # this trunk's c = 6 over BERT's c = 2
# (d) the system 0.00212-0.00213, 0.00196-0.00199, 0.00182-0.00184 and
# 0.00173-0.00175 by layer, equal to three digits on every seed (0.0017 is
# the output's one rounding to bf16; the rest the probabilities', rounded
# once for the second product); a bf16 softmax 0.0048 / 0.0041 / 0.0033 /
# 0.0038; the window left out 0.47-0.61 in the sliding layers.
CORE_ERROR_SHARE = 0.003
# (e) the system 0.0026-0.0046; no YaRN 0.458 on the full layer's q and k
# (its v 0.0044)
INPUT_DRIFT = 0.1


def logit_tolerances(params: dict, n_layers: int) -> dict:
    """``checks.logit_tolerances`` with this trunk's constant on the heads
    that read the deep state (the sigmoid regressors keep ``2 x 2^-7``)."""
    out = checks.logit_tolerances(params, n_layers)
    for key in ("start_class", "end_class", "cls"):
        out[key] *= ROUNDING_FACTOR
    return out


def attention_layers(tree: dict, holding: str = "attention_input") -> list:
    """``(layer name, its attention subtree)`` of the attention layers of the
    sown collection, in layer order."""
    layers = tree["transformer"]
    order = sorted((k for k in layers if k.startswith("layer_")
                    and holding in layers[k].get("attention", {})),
                   key=lambda k: int(k.rsplit("_", 1)[1]))
    return [(k, layers[k]["attention"]) for k in order]


def program(model):
    """The system as ``compare`` judges it: ``(parameters, inputs) ->
    (predictions, chosen [B, L, K] a layer, router inputs [B, L, hidden] a
    layer, ((q, k, v), core output) an attention layer)``, the last three as
    the layers sowed them."""
    def run(p, inputs):
        preds, sown = model.apply({"params": p}, **inputs, deterministic=True,
                                  mutable=[ROUTING])
        layers = expert_layers(sown[ROUTING])
        return (preds, [m["chosen"][0] for _, m in layers],
                [m["router_input"][0] for _, m in layers],
                [(a["attention_input"][0], a["attention_output"][0])
                 for _, a in attention_layers(sown[ROUTING])])
    return run


def core_report(read, wrote, mask, window) -> dict:
    """One attention layer's core output against the reference's core on the
    q, k and v it read: the RMS of the difference on real tokens over the RMS
    of the reference's output there, and the largest difference over that
    RMS."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        want = reference_mellum2.attention_core(*read, mask, window)
    off = jnp.asarray(wrote, jnp.float32) - want
    real = (jnp.asarray(mask) > 0)[:, :, None, None]
    count = jnp.sum(real) * want.shape[-2] * want.shape[-1]
    rms = jnp.sqrt(jnp.sum(jnp.where(real, want * want, 0.0)) / count)
    return {
        "error_rms_share":
            jnp.sqrt(jnp.sum(jnp.where(real, off * off, 0.0)) / count) / rms,
        "largest_error_over_rms":
            jnp.max(jnp.where(real, jnp.abs(off), 0.0)) / rms,
    }


def compare(trainer, cell, job: dict, params, seed: int,
            single_device: bool, *, system=None) -> dict:
    """Same arguments and report keys as
    ``runners.train.check_against_reference``. ``system``: what is judged in
    the place of ``program(trainer.model)``: the readings' lowered
    controls."""
    import jax
    import jax.numpy as jnp

    if not single_device:
        raise NotImplementedError(
            "checks_mellum2 compares on one chip; the trunk under data:N is "
            "the tests' (tests/test_mellum2.py)")
    cfg = cell.config if "model" not in job else job["reference_config"]
    seq = int(params.max_seq_len)
    lengths = [seq, (3 * seq) // 4, (2 * seq) // 5, max(8, seq // 7)]
    inputs, labels = checks.seeded_rows(seed, cfg["vocab_size"], seq, lengths)
    mask = inputs["attention_mask"]
    system = system or program(trainer.model)
    windows = [cfg["sliding_window"] if kind == "sliding_attention" else None
               for kind in cfg["layer_types"]]
    alone = jax.jit(core_report, static_argnums=3)
    route = jax.jit(lambda p, x: reference_mellum2.route(p, cfg, x)[:2])

    @jax.jit
    def ref(p, one, routing, reads):
        preds, own = reference_mellum2.forward(p, cfg, **one, routing=routing)
        return (preds, own["chosen"], own["margin"],
                [input_drift(read, theirs[0], one["attention_mask"])
                 for read, theirs in zip(reads, own["attention"])])

    rows = [{k: v[row:row + 1] for k, v in inputs.items()}
            for row in range(len(lengths))]
    # every stated rounding made: left to itself XLA reads a bf16 result it
    # has just rounded from f32 at its f32 value (excess precision), and a
    # core would be judged on other inputs than those sown
    with trainer.mesh:
        judged = jax.jit(system).lower(trainer.params, rows[0]).compile(
            compiler_options={"xla_allow_excess_precision": False})
    routers = [mlp["router"] for _, mlp in expert_layers(
        trainer.params, "router")]
    got, want, chosen, on_state, own, cores, drifts = ([] for _ in range(7))
    for one in rows:
        with trainer.mesh:
            preds, picked, router_inputs, attention = judged(
                trainer.params, one)
        got.append(jax.device_get(preds))
        # (d) each core alone, on the q, k and v the judged core read
        cores.append([
            {k: float(v) for k, v in alone(
                read, wrote, one["attention_mask"], window).items()}
            for (read, wrote), window in zip(attention, windows)])
        # (a) the router alone, on the state the judged router read
        on_state.append(jax.device_get(
            [route(p, x) for p, x in zip(routers, router_inputs)]))
        del router_inputs
        # (c) logits, the reference routed as the system routed, (b) what the
        # reference itself chooses and (e) what its cores read along that
        # trajectory
        preds, theirs, margins, drift = jax.device_get(ref(
            trainer.params, one, picked, [read for read, _ in attention]))
        del attention
        want.append(preds)
        chosen.append(jax.device_get(picked))
        own.append((theirs, margins))
        drifts.append([{k: float(v) for k, v in d.items()} for d in drift])

    stacked = lambda parts: {k: np.concatenate([r[k] for r in parts])  # noqa: E731
                            for k in parts[0]}
    by_layer = lambda rows: [np.concatenate(layer) for layer in zip(*rows)]  # noqa: E731
    got, want = stacked(got), stacked(want)
    chosen = by_layer(chosen)
    got_loss = float(trainer.loss(
        {k: jnp.asarray(v) for k, v in got.items()}, labels)[0])
    want_loss = float(reference_mellum2.loss(
        want, labels, smooth_alpha=float(params.smooth_alpha)))
    errors = checks.absolute_errors(got, want, mask)
    heads = jax.device_get({k: trainer.params[k] for k in (
        "position_outputs", "classifier")})
    tolerances = logit_tolerances(heads, int(cfg["num_hidden_layers"]))
    on_one_state = routing_report(
        chosen, by_layer([[r[0] for r in row] for row in on_state]),
        by_layer([[r[1] for r in row] for row in on_state]), mask)
    along = routing_report(
        chosen, by_layer([theirs for theirs, _ in own]),
        by_layer([margins for _, margins in own]), mask)
    # layer by layer, the worst of the rows
    worst = lambda rows: [  # noqa: E731
        {k: max(row[i][k] for row in rows) for k in row_0}
        for i, row_0 in enumerate(rows[0])]
    layers, drift = worst(cores), worst(drifts)
    failed = [part for part, ok in (
        ("router_on_one_state",
         all(r["differ_above_margin"] == 0 for r in on_one_state)),
        ("routing_along_the_trajectory",
         all(r["differ_share"] <= TRAJECTORY_DIFFER_SHARE for r in along)),
        ("logits", checks.within(errors, tolerances)),
        ("loss", checks.close(got_loss, want_loss, checks.LOSS_RTOL)),
        ("attention_on_one_input",
         all(r["error_rms_share"] <= CORE_ERROR_SHARE for r in layers)),
        ("attention_inputs_along_the_trajectory",
         all(v <= INPUT_DRIFT for r in drift for v in r.values())),
    ) if not ok]
    return {"logit_abs_err": errors, "logit_tol": tolerances,
            "loss": got_loss, "reference_loss": want_loss,
            "loss_rtol": checks.LOSS_RTOL,
            "routing": {
                "margin": ROUTING_MARGIN, "layers": on_one_state,
                "trajectory_differ_share_max": TRAJECTORY_DIFFER_SHARE,
                "trajectory_differ_share": [
                    r["differ_share"] for r in along],
                "trajectory_largest_margin_of_a_difference": [
                    r["largest_margin_of_a_difference"] for r in along]},
            "attention": {"kinds": list(cfg["layer_types"]),
                          "error_rms_share_max": CORE_ERROR_SHARE,
                          "layers": layers, "input_drift_max": INPUT_DRIFT,
                          "input_drift": drift},
            "failed_parts": failed, "ok": not failed}

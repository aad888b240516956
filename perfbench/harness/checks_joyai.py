"""The comparison that decides ``correct`` for the ``joyai_llm_flash`` trunk:
the system (bf16 matmuls, the timed kernels) against ``reference_joyai``
(float32, highest precision) on the runner's ragged seeded rows at the job's
sequence length. Top-8 of 256 is a discontinuous function of the hidden
state, and one flipped expert moves a logit by more than any rounding
tolerance, so the reference is run with the experts the system chose
(``routing=``) and the routing is judged apart, twice:

(a) **the router alone**, layer by layer, on the state the system's router
    read (sown next to its choice): the reference routes that same state in
    float32, and wherever its margin (8th less 9th biased score) exceeds
    ``ROUTING_MARGIN`` the system's chosen set must equal the reference's, on
    every real token. Two float32 evaluations of one dot product of 2,048
    terms differ by about 1e-6; a router computed in bf16 (scores 2^-9 to
    2^-8 apart) flips tokens at margins up to several 1e-3. The share of
    tokens under the margin is reported and not judged (it has no second
    reading to set a limit from). This part sees the router and nothing
    before it: the state is the program's own.
(b) **the routing along the reference's trajectory**: the share of a layer's
    real tokens whose chosen set differs from what the reference itself
    chooses there, on ITS float32 state (every layer before routed as the
    system routed, so one layer's differences do not compound into the
    next), may not pass ``TRAJECTORY_DIFFER_SHARE``. This is what an error
    anywhere upstream of a router moves: neighbouring scores near the 8th
    place lie 7e-3 apart in the mean and the bf16 state moves a score by
    about 1e-3, so the system differs on a tenth of the tokens; a state
    computed in the next precision down differs on several times that.
(c) **logits and loss**, the reference routed as the system routed: the form
    of ``checks.logit_tolerances``, ``c x 2^-7 x sqrt(layers) x |w|_2``, with
    ``c = 6`` where BERT's is 2. Every matmul here rounds both its weights
    and its input to bf16 (2^-9 / sqrt(3) relative, each), and a layer's
    longest path holds about nine of them (q_a, q_b, scores, context, output,
    gate/up, down, the residual adds) with no LayerNorm re-centring the
    stream: sqrt(2 x 45) x 1.1e-3 = 1.1% of a unit-variance state, whose
    largest of 19,000 values reads 0.046 |w|. Measured on the chip
    0.043-0.055 |w| (PR 27), so the margin is a factor of two, as BERT's is.
    float8 matmul inputs (the nearest precision below: 16 times the spacing)
    or a missing term (the 2.5 scale, ``norm_topk_prob``, the 1/sqrt(192),
    RoPE's pairing, the causal mask, the shared expert: tenths of a logit)
    land outside it. ``LOSS_RTOL`` as BERT's.

``compare`` names the parts that failed (``failed_parts``). The readings
behind the limits are in PERF.md (section 4); ``scripts/
joyai_tolerance_readings.py`` takes them by handing ``compare`` a lowered
system (``system=``): a bf16 router, float8 matmul inputs, partial sums kept
in bf16.
"""

from __future__ import annotations

import numpy as np

from . import checks, reference_joyai

ROUTING_MARGIN = 1e-4
TRAJECTORY_DIFFER_SHARE = 0.13
ROUNDING_FACTOR = 3.0       # this trunk's c = 6 over BERT's c = 2
ROUTING = "routing"     # the collection the program's expert layers sow into


def logit_tolerances(params: dict, n_layers: int) -> dict:
    """``checks.logit_tolerances`` with this trunk's constant on the heads
    that read the deep state (the sigmoid regressors keep ``2 x 2^-7``)."""
    out = checks.logit_tolerances(params, n_layers)
    for key in ("start_class", "end_class", "cls"):
        out[key] *= ROUNDING_FACTOR
    return out


def expert_layers(tree: dict, holding: str = "chosen") -> list:
    """``(layer name, its mlp subtree)`` of the expert layers of the sown
    ``routing`` collection (or, with ``holding='router'``, of the
    parameters), in layer order."""
    layers = tree["transformer"]
    order = sorted((k for k in layers if k.startswith("layer_")
                    and holding in layers[k].get("mlp", {})),
                   key=lambda k: int(k.rsplit("_", 1)[1]))
    return [(k, layers[k]["mlp"]) for k in order]


def routing_report(system_chosen, reference_chosen, margins, mask) -> list:
    """Layer by layer over the real tokens: the share whose chosen sets
    differ, those of them above the margin, the largest margin at which any
    differ (the reading ``ROUTING_MARGIN`` is set from) and the share under
    the margin."""
    real = np.asarray(mask, bool)
    out = []
    for got, want, margin in zip(system_chosen, reference_chosen, margins):
        got = np.sort(np.asarray(got)[real], axis=-1)
        want = np.sort(np.asarray(want)[real], axis=-1)
        margin = np.asarray(margin)[real]
        differ = (got != want).any(-1)
        out.append({
            "tokens": int(real.sum()),
            "under_margin_share": float((margin <= ROUTING_MARGIN).mean()),
            "differ_share": float(differ.mean()),
            "differ_above_margin": int(
                (differ & (margin > ROUTING_MARGIN)).sum()),
            "largest_margin_of_a_difference": float(
                margin[differ].max()) if differ.any() else 0.0,
        })
    return out


def program(model):
    """The system as ``compare`` judges it: ``(parameters, inputs) ->
    (predictions, chosen [B, L, K] a layer, router inputs [B, L, hidden] a
    layer)``, the last two as the expert layers sowed them."""
    def run(p, inputs):
        preds, sown = model.apply({"params": p}, **inputs, deterministic=True,
                                  mutable=[ROUTING])
        layers = expert_layers(sown[ROUTING])
        return (preds, [m["chosen"][0] for _, m in layers],
                [m["router_input"][0] for _, m in layers])
    return run


def compare(trainer, cell, job: dict, params, seed: int,
            single_device: bool, *, system=None) -> dict:
    """Same arguments and report keys as
    ``runners.train.check_against_reference``. ``system``: what is judged in
    the place of ``program(trainer.model)``: the readings' lowered
    controls."""
    import jax

    if not single_device:
        raise NotImplementedError(
            "checks_joyai compares on one chip; the trunk under data:N is "
            "the tests' (tests/test_mla_moe.py)")
    cfg = cell.config if "model" not in job else job["reference_config"]
    seq = int(params.max_seq_len)
    lengths = [seq, (3 * seq) // 4, (2 * seq) // 5, max(8, seq // 7)]
    inputs, labels = checks.seeded_rows(seed, cfg["vocab_size"], seq, lengths)
    mask = inputs["attention_mask"]
    loss_fn = trainer.loss
    system = system or program(trainer.model)

    def judged(p, inputs, labels):
        preds, chosen, router_inputs = system(p, inputs)
        return preds, loss_fn(preds, labels)[0], chosen, router_inputs

    with trainer.mesh:
        got, got_loss, chosen, router_inputs = jax.jit(judged)(
            trainer.params, inputs, labels)
    got, got_loss, chosen = jax.device_get((got, got_loss, chosen))
    host_params = jax.device_get(trainer.params)

    # (a) the router alone, on the state the judged router read
    route = jax.jit(lambda p, x: reference_joyai.route(p, cfg, x)[:2])
    routed = [jax.device_get(route(mlp["router"], x)) for (_, mlp), x in zip(
        expert_layers(host_params, "router"), router_inputs)]
    del router_inputs
    on_one_state = routing_report(chosen, [r[0] for r in routed],
                                  [r[1] for r in routed], mask)

    # (c) logits and loss, the reference routed as the system routed, and
    # (b) what the reference itself chooses along that trajectory
    def ref(p, i, r):
        preds, own = reference_joyai.forward(p, cfg, **i, routing=r)
        return preds, own["chosen"], own["margin"]

    want, own_chosen, own_margin = jax.device_get(
        jax.jit(ref)(host_params, inputs, chosen))
    want_loss = float(reference_joyai.loss(
        want, labels, smooth_alpha=float(params.smooth_alpha)))
    errors = checks.absolute_errors(got, want, mask)
    tolerances = logit_tolerances(host_params, int(cfg["num_hidden_layers"]))
    along = routing_report(chosen, own_chosen, own_margin, mask)
    failed = [part for part, ok in (
        ("router_on_one_state",
         all(r["differ_above_margin"] == 0 for r in on_one_state)),
        ("routing_along_the_trajectory",
         all(r["differ_share"] <= TRAJECTORY_DIFFER_SHARE for r in along)),
        ("logits", checks.within(errors, tolerances)),
        ("loss", checks.close(float(got_loss), want_loss, checks.LOSS_RTOL)),
    ) if not ok]
    return {"logit_abs_err": errors, "logit_tol": tolerances,
            "loss": float(got_loss), "reference_loss": want_loss,
            "loss_rtol": checks.LOSS_RTOL,
            "routing": {
                "margin": ROUTING_MARGIN, "layers": on_one_state,
                "trajectory_differ_share_max": TRAJECTORY_DIFFER_SHARE,
                "trajectory_differ_share": [
                    r["differ_share"] for r in along],
                "trajectory_largest_margin_of_a_difference": [
                    r["largest_margin_of_a_difference"] for r in along]},
            "failed_parts": failed, "ok": not failed}

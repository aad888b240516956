"""The comparison that decides ``correct`` for the ``lfm2_moe`` trunk: the
system (bf16 matmuls, the timed kernels) against ``reference_lfm2`` (float32,
highest precision) on the runner's ragged seeded rows at the job's sequence
length. Top-4 of 32 is a discontinuous function of the hidden state, and one
flipped expert moves a logit by more than any rounding tolerance, so the
reference is run with the experts the system chose (``routing=``) and the
routing is judged apart, as ``checks_joyai`` does (its docstring has the
argument in full); the short convolution is judged apart too, because a logit
cannot tell its f32 gating from a bf16 one:

(a) **the router alone**, layer by layer, on the state the system's router
    read (sown next to its choice): wherever the reference's margin (4th less
    5th biased score) exceeds ``ROUTING_MARGIN`` the chosen sets must be
    equal, on every real token. Two f32 evaluations of one dot product of
    2,048 terms differ by about 1e-6; a router computed in bf16 flips tokens
    at margins up to several 1e-3.
(b) **the routing along the reference's trajectory**: the share of a layer's
    real tokens whose chosen set differs from what the reference itself
    chooses on ITS float32 state (every layer before routed as the system
    routed) may not pass ``TRAJECTORY_DIFFER_SHARE``. With 32 experts the
    scores near the 4th place lie further apart than joyai's 256 do near the
    8th, so fewer tokens sit close enough for the bf16 state to flip them:
    the system reads 3.0-4.0% of a layer's 18,780 real tokens (joyai's
    8-11%), matmul partial sums kept in bf16 between tiles of 128 read
    6.0-6.6%, float8 matmul inputs 99%; the limit of 5% stands six of the
    readings' standard deviations (0.25 and 0.2 points over the seeds) from
    either side (PERF.md section 4).
(c) **logits and loss**, the reference routed as the system routed: the form
    of ``checks.logit_tolerances``, ``c x 2^-7 x sqrt(layers) x |w|_2``, with
    the ``c`` that ``ROUNDING_FACTOR`` gives. A layer's longest path holds
    six to eight bf16 matmuls (in_proj, out_proj or q/k, scores, context,
    output; gate/up, down) and no LayerNorm re-centres the stream, as in
    joyai's trunk. Measured on the chip 0.045-0.054 |w| against limits of
    0.105-0.107 (PR 31), a factor of two as BERT's and joyai's. float8 matmul
    inputs (the nearest precision below: 1.8-3.8) or a missing term (a gate, the taps' order, the q/k norm, RoPE's pairing, the
    head grouping, ``norm_topk_prob``: tenths of a logit) land outside it.
    ``LOSS_RTOL`` as BERT's.
(d) **the short convolution alone**, conv layer by conv layer, on the
    projection the system's operator read (sown next to what it wrote): the
    configuration states the gating and the taps in f32 with ONE rounding, of
    the result, to the compute dtype. So the system's output lies within one
    bf16 rounding (``2^-8`` relative) of the f32 result wherever the sum does
    not cancel, and the share of elements on real tokens that lie further may
    not pass ``CONV_BEYOND_ONE_ROUNDING``. Gating and taps computed in bf16
    round three or four times and put 36% of the elements beyond; the system
    puts none (PR 31, on the chip). The judged forward is compiled with
    ``xla_allow_excess_precision`` off: left to itself XLA hands the
    convolution the projection's f32 value and not the bf16 one it has just
    rounded and sown (a third of the elements then read beyond, for an input
    that differs and not for the operator's own arithmetic).

``compare`` names the parts that failed (``failed_parts``). The readings
behind the limits are in PERF.md (section 4); ``scripts/
lfm2_tolerance_readings.py`` takes them by handing ``compare`` a lowered
system (``system=``): a bf16 router, float8 matmul inputs, partial sums kept
in bf16, the convolution's elementwise results rounded to bf16.
"""

from __future__ import annotations

import numpy as np

from . import checks, reference_lfm2
from .checks_joyai import ROUTING, ROUTING_MARGIN, expert_layers, routing_report

TRAJECTORY_DIFFER_SHARE = 0.05
ROUNDING_FACTOR = 3.0       # this trunk's c = 6 over BERT's c = 2
ONE_ROUNDING = 2.0 ** -8    # bf16, round to nearest, relative
CONV_BEYOND_ONE_ROUNDING = 1e-3


def logit_tolerances(params: dict, n_layers: int) -> dict:
    """``checks.logit_tolerances`` with this trunk's constant on the heads
    that read the deep state (the sigmoid regressors keep ``2 x 2^-7``)."""
    out = checks.logit_tolerances(params, n_layers)
    for key in ("start_class", "end_class", "cls"):
        out[key] *= ROUNDING_FACTOR
    return out


def conv_layers(tree: dict, holding: str = "conv_input") -> list:
    """``(layer name, its conv subtree)`` of the convolution layers of the
    sown collection (or, with ``holding='taps'``, of the parameters), in
    layer order."""
    layers = tree["transformer"]
    order = sorted((k for k in layers if k.startswith("layer_")
                    and holding in layers[k].get("conv", {})),
                   key=lambda k: int(k.rsplit("_", 1)[1]))
    return [(k, layers[k]["conv"]) for k in order]


def program(model):
    """The system as ``compare`` judges it: ``(parameters, inputs) ->
    (predictions, chosen [B, L, K] a layer, router inputs [B, L, hidden] a
    layer, (conv input [B, L, 3 hidden], conv output [B, L, hidden]) a conv
    layer)``, the last three as the layers sowed them."""
    def run(p, inputs):
        preds, sown = model.apply({"params": p}, **inputs, deterministic=True,
                                  mutable=[ROUTING])
        layers = expert_layers(sown[ROUTING])
        return (preds, [m["chosen"][0] for _, m in layers],
                [m["router_input"][0] for _, m in layers],
                [(c["conv_input"][0], c["conv_output"][0])
                 for _, c in conv_layers(sown[ROUTING])])
    return run


def conv_report(taps, read, wrote, mask) -> dict:
    """One conv layer's output against the f32 result on the input it read:
    the share of elements on real tokens further from it than one rounding
    (a hundredth of one for the two evaluations' own f32 noise), and the
    largest distance in roundings where the result is not a cancelled sum."""
    import jax.numpy as jnp

    want = reference_lfm2.gated_conv(
        jnp.asarray(read, jnp.float32), jnp.asarray(taps, jnp.float32))
    off = jnp.abs(jnp.asarray(wrote, jnp.float32) - want)
    size = jnp.abs(want)
    real = (jnp.asarray(mask) > 0)[:, :, None]
    beyond = (off > 1.01 * ONE_ROUNDING * size + 1e-30) & real
    whole = real & (size > 1e-3 * jnp.sqrt(jnp.mean(want * want)))
    return {
        "beyond_one_rounding_share":
            jnp.sum(beyond) / (jnp.sum(real) * want.shape[-1]),
        "largest_distance_in_roundings": jnp.max(
            jnp.where(whole, off / (ONE_ROUNDING * size + 1e-30), 0.0)),
    }


def compare(trainer, cell, job: dict, params, seed: int,
            single_device: bool, *, system=None) -> dict:
    """Same arguments and report keys as
    ``runners.train.check_against_reference``. ``system``: what is judged in
    the place of ``program(trainer.model)``: the readings' lowered
    controls."""
    import jax

    if not single_device:
        raise NotImplementedError(
            "checks_lfm2 compares on one chip; the trunk under data:N is "
            "the tests' (tests/test_lfm2.py)")
    cfg = cell.config if "model" not in job else job["reference_config"]
    seq = int(params.max_seq_len)
    lengths = [seq, (3 * seq) // 4, (2 * seq) // 5, max(8, seq // 7)]
    inputs, labels = checks.seeded_rows(seed, cfg["vocab_size"], seq, lengths)
    mask = inputs["attention_mask"]
    loss_fn = trainer.loss
    system = system or program(trainer.model)

    def judged(p, inputs, labels):
        preds, chosen, router_inputs, convs = system(p, inputs)
        return preds, loss_fn(preds, labels)[0], chosen, router_inputs, convs

    # every stated rounding made: left to itself XLA reads a bf16 result it
    # has just rounded from f32 at its f32 value (excess precision), and the
    # convolution would be judged on another input than the one sown
    with trainer.mesh:
        got, got_loss, chosen, router_inputs, convs = jax.jit(judged).lower(
            trainer.params, inputs, labels).compile(
            compiler_options={"xla_allow_excess_precision": False})(
            trainer.params, inputs, labels)
    got, got_loss, chosen = jax.device_get((got, got_loss, chosen))
    host_params = jax.device_get(trainer.params)

    # (d) the convolution alone, on the projection the judged operator read
    alone = jax.jit(conv_report)
    conv_alone = [
        {k: float(v) for k, v in alone(
            conv["taps"], read, wrote, mask).items()}
        for (_, conv), (read, wrote) in zip(
            conv_layers(host_params, "taps"), convs)]
    del convs

    # (a) the router alone, on the state the judged router read
    route = jax.jit(lambda p, x: reference_lfm2.route(p, cfg, x)[:2])
    routed = [jax.device_get(route(mlp["router"], x)) for (_, mlp), x in zip(
        expert_layers(host_params, "router"), router_inputs)]
    del router_inputs
    on_one_state = routing_report(chosen, [r[0] for r in routed],
                                  [r[1] for r in routed], mask)

    # (c) logits and loss, the reference routed as the system routed, and
    # (b) what the reference itself chooses along that trajectory
    def ref(p, i, r):
        preds, own = reference_lfm2.forward(p, cfg, **i, routing=r)
        return preds, own["chosen"], own["margin"]

    want, own_chosen, own_margin = jax.device_get(
        jax.jit(ref)(host_params, inputs, chosen))
    want_loss = float(reference_lfm2.loss(
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
        ("conv_on_one_input",
         all(r["beyond_one_rounding_share"] <= CONV_BEYOND_ONE_ROUNDING
             for r in conv_alone)),
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
            "conv": {"beyond_one_rounding_share_max": CONV_BEYOND_ONE_ROUNDING,
                     "layers": conv_alone},
            "failed_parts": failed, "ok": not failed}

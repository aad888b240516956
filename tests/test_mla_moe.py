"""The ``joyai_llm_flash`` trunk (MLA + routed experts with a shared expert) at
the tiny preset on the CPU: the system against the in-repo plain reference
(``perfbench/harness/reference_joyai.py``) for logits, loss and gradients;
each term of the mathematics dropped in turn must land outside the
benchmark's tolerance; the experts' shares add up to the uncut layer; a
routing that overflows a chunk is not dropped; the fused causal kernel in
interpret mode against XLA; and through the ``Trainer``: the router's
selection bias stays put, expert leaves survive a checkpoint, ``data:2``
gives the one-device loss.
"""

import dataclasses
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from ml_recipe_tpu.data.collate import make_collate_fun  # noqa: E402
from ml_recipe_tpu.data.datasets import DummyDataset  # noqa: E402
from ml_recipe_tpu.losses import build_loss  # noqa: E402
from ml_recipe_tpu.models import MODEL_PRESETS, QAModel  # noqa: E402
from ml_recipe_tpu.models import mla_moe  # noqa: E402
from ml_recipe_tpu.ops import expert_ffn  # noqa: E402
from ml_recipe_tpu.parallel import build_mesh  # noqa: E402
from ml_recipe_tpu.train import Trainer  # noqa: E402
from perfbench.harness import checks, reference_joyai  # noqa: E402

from helpers import make_tokenizer  # noqa: E402

TINY = MODEL_PRESETS["joyai-tiny"]
L = 32


def ref_cfg(cfg=TINY, **over):
    """The configuration file's keys for a ``DecoderConfig``."""
    out = {
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "experts_held": {"first": cfg.experts_first,
                         "count": cfg.experts_held,
                         "of": cfg.n_routed_experts},
    }
    out.update(over)
    return out


@pytest.fixture(scope="module")
def seeded():
    """Model, seeded weights moved off their initial scale (at 0.02 the
    attention scores are hundredths and a dropped 1/sqrt(d) or RoPE would
    not show), ragged rows and labels."""
    model = QAModel(TINY, dtype=jnp.float32, attention_impl="xla")
    inputs, labels = checks.seeded_rows(5, TINY.vocab_size, L, [L, 20, 13, 7])
    params = model.init(jax.random.key(1), inputs["input_ids"])["params"]

    def widen(path, x):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "kernel" and "attention" in names:
            return x * 12.0
        if "router" in names and names[-1] == "kernel":
            return x * 4.0
        if names[-1] in ("gate", "up", "down", "kernel", "embedding"):
            return x * 4.0
        return x

    params = jax.tree_util.tree_map_with_path(widen, params)
    heads = jax.random.split(jax.random.key(2), 4)
    for key, name in zip(heads, ("position_outputs", "classifier",
                                 "reg_start", "reg_end")):
        params[name]["bias"] = 0.1 * jax.random.normal(
            key, params[name]["bias"].shape)
    return model, jax.device_get(params), inputs, labels


def system_outputs(model, params, inputs):
    with jax.default_matmul_precision("highest"):
        return model.apply({"params": params}, **inputs, deterministic=True)


def recipe_loss():
    return build_loss(types.SimpleNamespace(loss="smooth", smooth_alpha=0.01))


def test_system_matches_the_reference_logits_loss_and_gradients(seeded):
    model, params, inputs, labels = seeded
    got = system_outputs(model, params, inputs)
    want, own = reference_joyai.forward(params, ref_cfg(), **inputs,
                                        q_block=16)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert max(errors.values()) < 2e-5, errors      # float32 against float32
    assert len(own["chosen"]) == TINY.num_layers - 1
    loss_fn = recipe_loss()
    device_labels = {k: jnp.asarray(v) for k, v in labels.items()}

    def system_loss(p):
        return loss_fn(system_outputs(model, p, inputs), device_labels)[0]

    def reference_loss(p):
        preds, _ = reference_joyai.forward(p, ref_cfg(), **inputs, q_block=16)
        return reference_joyai.loss(preds, labels, smooth_alpha=0.01)

    loss, grads = jax.value_and_grad(system_loss)(params)
    want_loss, want_grads = jax.value_and_grad(reference_loss)(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, (
            jax.tree_util.keystr(path))
    # the selection bias gets no gradient at all
    bias = grads["transformer"]["layer_1"]["mlp"]["router"]["bias"]
    assert float(jnp.abs(bias).max()) == 0.0


def _drop_bias(params, cfg, monkeypatch):
    for name, layer in params["transformer"].items():
        if "router" in layer.get("mlp", {}):
            layer["mlp"]["router"]["bias"] = np.zeros_like(
                layer["mlp"]["router"]["bias"])
    return cfg


def _drop_norm_topk(params, cfg, monkeypatch):
    return dict(cfg, norm_topk_prob=False)


def _drop_routed_scale(params, cfg, monkeypatch):
    return dict(cfg, routed_scaling_factor=1.0)


def _drop_score_scale(params, cfg, monkeypatch):
    monkeypatch.setattr(reference_joyai, "_score_scale", lambda d: 1.0)
    return cfg


def _drop_interleaving(params, cfg, monkeypatch):
    """Pairs ``(x[i], x[i + d/2])`` in place of ``(x[2i], x[2i + 1])``: what
    ``rope_interleave`` left unread would compute."""
    def half_split(x, theta):
        d = x.shape[-1]
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * (
            theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
        angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3)
                              + (d // 2,))
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                a * jnp.sin(angle) + b * jnp.cos(angle)], -1)

    monkeypatch.setattr(reference_joyai, "_rope", half_split)
    return cfg


def _drop_causal(params, cfg, monkeypatch):
    monkeypatch.setattr(
        reference_joyai, "_causal",
        lambda rows, length: jnp.ones((rows.shape[0], length), bool))
    return cfg


@pytest.mark.parametrize("drop", [
    _drop_bias, _drop_norm_topk, _drop_routed_scale, _drop_score_scale,
    _drop_interleaving, _drop_causal], ids=lambda f: f.__name__[6:])
def test_a_dropped_term_lands_outside_the_benchmarks_tolerance(
        seeded, drop, monkeypatch):
    model, params, inputs, labels = seeded
    got = system_outputs(model, params, inputs)
    broken = jax.tree_util.tree_map(np.array, params)
    cfg = drop(broken, ref_cfg(), monkeypatch)
    # routed as the system routed where the routing itself is not the term
    want, _ = reference_joyai.forward(broken, cfg, **inputs, q_block=16)
    tolerances = checks.logit_tolerances(params, TINY.num_layers)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert not checks.within(errors, tolerances), (errors, tolerances)


def _readings():
    """``scripts/joyai_tolerance_readings.py``: the lowered controls whose
    verdicts on the chip set the comparison's limits."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "joyai_tolerance_readings",
        REPO / "scripts" / "joyai_tolerance_readings.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("control,caught_by", [
    (None, []),
    ("bf16_router", ["router_on_one_state"]),
    ("float8_matmuls", ["router_on_one_state",
                        "routing_along_the_trajectory", "logits", "loss"]),
    ("bf16_partial_sums", ["logits"]),
])
def test_the_comparison_passes_the_system_and_names_what_catches_a_control(
        seeded, control, caught_by):
    """``checks_joyai.compare`` itself, at the tiny preset in float32: the
    system is correct; the router in bf16 where the configuration states f32
    is caught by the router part alone (on one state nothing else moved);
    float8 matmul inputs by every part; partial sums kept in
    bf16 where the configuration states f32 accumulation (between tiles of 8
    here, of 128 at the published widths) by the logits."""
    from perfbench.harness import checks_joyai

    model, params, _, _ = seeded
    seq = 128
    cfg = ref_cfg(vocab_size=TINY.vocab_size)
    trainer = types.SimpleNamespace(
        model=model, loss=recipe_loss(), mesh=build_mesh("data:1"),
        params=params)
    flags = types.SimpleNamespace(max_seq_len=seq, smooth_alpha=0.01)
    system = None if control is None else _readings().controls(
        model, cfg, tile=8)[control]
    with jax.default_matmul_precision("highest"):
        report = checks_joyai.compare(
            trainer, None, {"model": "joyai-tiny", "reference_config": cfg},
            flags, 7, True, system=system)
    assert report["failed_parts"] == caught_by, report
    assert report["ok"] == (control is None)
    if control is None:
        assert max(report["routing"]["trajectory_differ_share"]) == 0.0
        assert all(r["differ_share"] == 0.0 and r["tokens"] == 293
                   for r in report["routing"]["layers"])


def test_the_readings_script_runs_at_the_tiny_size(capsys):
    """``--rehearse``: the script's own path (the cell's tiny configuration,
    bf16) through ``compare``, the grouped matmul's rounding and the held
    experts' load with the states' common component taken out."""
    import json

    assert _readings().main(
        ["--rehearse", "--seeds", "2700000913", "--controls"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert list(said["verdicts"]) == ["system"]
    assert said["verdicts"]["system"]["ok"], said["verdicts"]["system"]
    rounding = said["grouped_matmul_bf16_result_against_f32_rounded_once"]
    assert rounding["of"] > 0
    # XLA's CPU dot may round a bf16 result twice: a handful of elements
    assert rounding["elements_that_differ"] <= rounding["of"] // 1000
    assert [set(layer) for layer in said["held_load"]] == [{
        "as_routed", "common_component_removed",
        "common_component_norm_over_state_norm"}] * 2


def test_the_held_assignments_script_runs_at_the_tiny_size(capsys):
    """``scripts/moe_held_readings.py --rehearse``: per seed, micro-batch and
    expert layer the assignments held over the expectation, then what the
    chunk sizes make of them."""
    import json

    sys.path.insert(0, str(REPO / "scripts"))
    import moe_held_readings

    assert moe_held_readings.main([
        "--rehearse", "--cells", "joyai-ep16-train-seq4096", "--split", "2",
        "--seeds", "1", "2"]) == 0
    *per_seed, said = map(json.loads, capsys.readouterr().out.splitlines())
    assert [line["seed"] for line in per_seed] == [1, 2]
    # two micro-batches of two expert layers, 4 of 8 held at top-2
    ratios = [r for line in per_seed for micro in line["held_over_expected"]
              for r in micro]
    assert len(ratios) == 8 == said["instances"]
    assert all(0.4 < r < 1.6 for r in ratios)
    assert (said["first_chunk"], said["granule"]) == expert_ffn.chunk_sizes(
        said["tokens"], 2, 4, 8)
    assert said["expected"] == said["tokens"]
    assert said["over_the_first_chunk"] == sum(
        r * said["expected"] > said["first_chunk"] for r in ratios)
    assert said["min"] == pytest.approx(min(ratios), abs=1e-4)


# -- the expert layer alone ----------------------------------------------------------

def _layer_params(cfg, key):
    layer = mla_moe.ExpertLayer(cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.hidden_size))
    params = layer.init(key, x)["params"]
    params = jax.tree_util.tree_map(lambda p: p * 4.0, params)
    return layer, jax.device_get(params), x


def _reference_layer(params, cfg, x):
    with jax.default_matmul_precision("highest"):
        return reference_joyai._expert_layer(params, ref_cfg(cfg), x)[0]


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips of four experts each against the reference holding all
    eight: the routed parts add up, the shared expert is counted once."""
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=8)
    _, params, x = _layer_params(whole, jax.random.key(3))
    want = _reference_layer(params, whole, x)
    shared = reference_joyai._swiglu(params["shared_expert"], x)
    total = shared
    for first in (0, 4):
        share = dataclasses.replace(TINY, experts_first=first, experts_held=4)
        held = dict(params, experts=jax.tree_util.tree_map(
            lambda w: w[first:first + 4], params["experts"]))
        with jax.default_matmul_precision("highest"):
            part = mla_moe.ExpertLayer(share, jnp.float32).apply(
                {"params": held}, x)
        assert float(jnp.abs(part - _reference_layer(held, share, x)).max()) \
            < 1e-5
        total = total + (part - shared)
    assert float(jnp.abs(total - want).max()) < 2e-5


@pytest.mark.parametrize("both_held", [False, True],
                         ids=["one_granule_over", "five_granules_over"])
def test_every_token_on_one_held_expert_is_not_dropped(both_held):
    """The router sends every token to the same two experts: one of them
    held (T rows against a first chunk of 0.75 T: one granule more), or both
    (2T rows, the worst case: five granules round the loop). Output and
    gradients still match the reference."""
    # holds experts 3..4 of 8, top-2: T / 2 rows expected of T = 32 tokens, so
    # the first chunk is 1.5 x 16 = 24 rows and a granule 4 -> 8
    cfg = dataclasses.replace(TINY, experts_first=3, experts_held=2)
    layer, params, x = _layer_params(cfg, jax.random.key(4))
    bias = np.zeros(8, np.float32)
    bias[3], bias[4 if both_held else 7] = 3.0, 2.0
    params["router"]["bias"] = bias
    tokens = x.shape[0] * x.shape[1]

    def system(p, x):
        with jax.default_matmul_precision("highest"):
            out, sown = layer.apply({"params": p}, x,
                                    mutable=[mla_moe.ROUTING])
        return jnp.sum(out ** 2), sown[mla_moe.ROUTING]["stats"][0]

    def reference(p, x):
        return jnp.sum(_reference_layer(p, cfg, x) ** 2)

    (got, stats), grads = jax.value_and_grad(system, (0, 1), has_aux=True)(
        params, x)
    want, want_grads = jax.value_and_grad(reference, (0, 1))(params, x)
    assert float(stats["moe_held_assignments"]) == tokens * (1 + both_held)
    assert float(stats["moe_overflow_chunks"]) == (5 if both_held else 1)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * float(
            jnp.abs(w).max()) + 1e-7


def _dense_routed(x, weights, w_gate_up, w_down, chosen, first):
    """The reference's sum (``reference_joyai._expert_layer``): every held
    expert over every token, weighted by the slots that chose it."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate_up.shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        gate, up = jnp.split(x @ w_gate_up[e], 2, axis=-1)
        y = y + w_e[:, None] * ((jax.nn.silu(gate) * up) @ w_down[e])
    return y


# 42 tokens at top-2, experts 2..5 of 8 held: 42 rows expected, so the first
# chunk is 63 -> 64 rows and a granule 11 -> 16; the 84 slots are filled to
# 64 + 2 x 16
@pytest.mark.parametrize("n_held, granules", [
    (63, 0), (64, 0), (65, 1), (80, 1), (81, 2), (84, 2)], ids=[
    "one_row_under", "the_first_chunk_full", "one_row_over",
    "at_a_granules_edge", "one_row_over_a_granule", "every_slot_held"])
def test_rows_beyond_the_first_chunk_go_in_granules(n_held, granules):
    """Exactly ``n_held`` of the 84 slots choose a held expert: value and
    gradients against the f32 reference, and what the two counters read."""
    T, K, H, F, first, count = 42, 2, 16, 8, 2, 4
    assert expert_ffn.chunk_sizes(T, K, count, 8) == (64, 16)
    slot = np.arange(T * K).reshape(T, K)
    held = np.stack([2 + slot[:, 0] // K % 2, 4 + slot[:, 1] // K % 2], -1)
    chosen = jnp.asarray(np.where(slot < n_held, held, [0, 7]))
    rng = np.random.default_rng(n_held)
    x, weights, w_gate_up, w_down = (
        jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in (
            (T, H), (T, K), (count, H, 2 * F), (count, F, H)))

    def system(*operands):
        plan = expert_ffn.make_plan(chosen, operands[1], first, count, of=8)
        y = expert_ffn.routed_experts(*operands, plan)
        return jnp.sum(y ** 2), (expert_ffn.routing_stats(plan), plan)

    def reference(*operands):
        return jnp.sum(_dense_routed(*operands, chosen, first) ** 2)

    operands = (x, weights, w_gate_up, w_down)
    with jax.default_matmul_precision("highest"):
        (got, (stats, plan)), grads = jax.value_and_grad(
            system, (0, 1, 2, 3), has_aux=True)(*operands)
        want, want_grads = jax.value_and_grad(reference, (0, 1, 2, 3))(
            *operands)
    assert int(plan.n_held) == n_held
    assert plan.order.shape == (64 + 2 * 16,) == plan.row_weight.shape
    assert float(stats["moe_overflow_chunks"]) == granules
    assert float(stats["moe_filler_share"]) == pytest.approx(
        1 - n_held / (64 + 16 * granules))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, want_grads):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * float(
            jnp.abs(w).max()) + 1e-7


def test_routing_counters_read_what_the_plan_holds():
    chosen = jnp.asarray([[0, 2], [2, 3], [7, 5], [2, 4]])
    plan = expert_ffn.make_plan(chosen, jnp.ones((4, 2)), first=2, count=4,
                                of=8)
    stats = expert_ffn.routing_stats(plan)
    assert int(plan.n_held) == 6            # experts 2, 2, 3, 5, 2, 4
    assert float(stats["moe_held_share"]) == pytest.approx(6 / 8)
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(3 / 1.5)
    assert np.asarray(plan.offsets).tolist() == [0, 3, 4, 5, 6]
    # 4 rows expected: a first chunk of 8 rows (T x K, no granule can follow)
    assert (plan.capacity, plan.granule) == (8, 8)
    assert float(stats["moe_overflow_chunks"]) == 0
    assert float(stats["moe_filler_share"]) == pytest.approx(2 / 8)


def test_the_step_sums_the_granules_and_averages_the_filler_share():
    """``step_stats`` over two expert layers, one within its first chunk and
    one two granules over it; the telemetry's histograms take both."""
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    T, K = 32, 2
    held = np.stack([np.full(T, 2), np.full(T, 4)], -1)
    layers = {}
    for name, n_held in (("layer_1", 30), ("layer_2", 60)):
        chosen = np.where(np.arange(T * K).reshape(T, K) < n_held, held,
                          [0, 7])
        plan = expert_ffn.make_plan(
            jnp.asarray(chosen), jnp.ones((T, K)), first=2, count=4, of=8)
        layers[name] = {"mlp": {"stats": (expert_ffn.routing_stats(plan),)}}
    layers["layer_0"] = {"mlp": {}}         # a dense layer sows nothing
    step = {k: float(v) for k, v in mla_moe.step_stats(
        {"transformer": layers}).items()}
    assert set(step) == set(mla_moe.STEP_STAT_KEYS)
    assert set(mla_moe.STEP_STAT_SUMS) == {
        "moe_held_assignments", "moe_overflow_chunks"}
    # 32 rows expected: a first chunk of 48 rows and granules of 8
    assert step["moe_held_assignments"] == 90
    assert step["moe_overflow_chunks"] == 0 + 2
    assert step["moe_filler_share"] == pytest.approx(
        ((1 - 30 / 48) + (1 - 60 / 64)) / 2)
    assert step["moe_held_share"] == pytest.approx((30 + 60) / 2 / 64)
    tele = TrainTelemetry()
    tele.observe_scalars(step)
    for key in mla_moe.STEP_STAT_KEYS:
        series = tele.registry.get("train_" + key)
        assert series.count == 1 and series.sum == pytest.approx(step[key])


# -- the fused causal kernel -----------------------------------------------------------

def _causal_case(length, d_qk, d_v, dtype, seed=0):
    """q, k, v in ``dtype``, a key mask whose second row's padding starts
    inside a block (150 of 256 in blocks of 128; 600 of 768 in blocks of
    256), and f32 weights of the output that are zero on pad rows."""
    B, H = 2, 2
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(B, length, H, d_qk)), dtype)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, length, H, d_v)), dtype)
    real = {256: 150, 768: 600}[length]
    mask = jnp.asarray((np.arange(length)[None, :]
                        < np.array([length, real])[:, None]).astype(np.int32))
    weigh = jnp.asarray(rng.normal(size=(B, length, H, d_v)), jnp.float32) \
        * mask[:, :, None, None]
    return q, k, v, mask, weigh


def _causal_grads(attend, q, k, v, weigh):
    def weighed(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) * weigh)
    return jax.grad(weighed, (0, 1, 2))(q, k, v)


# L 256 is two blocks of 128; L 768 three of 256, so a q block's dq is
# revisited from several k blocks and the last k block's dk/dv finish on the
# pair that starts them. "split" forces the two-kernel backward by a budget
# of 0.
CAUSAL_CASES = [
    (256, 192, 128, "float32", "fused"), (256, 192, 128, "float32", "split"),
    (768, 192, 128, "float32", "fused"), (768, 192, 128, "float32", "split"),
    (768, 128, 128, "float32", "fused"), (256, 128, 128, "float32", "split"),
    (768, 192, 128, "bfloat16", "fused"), (768, 192, 128, "bfloat16", "split"),
    (256, 128, 128, "bfloat16", "fused"),
]


@pytest.mark.parametrize(
    "length, d_qk, d_v, dtype, backward", CAUSAL_CASES,
    ids=lambda value: str(value))
def test_causal_two_width_kernel_matches_xla_forward_and_backward(
        length, d_qk, d_v, dtype, backward, monkeypatch):
    from ml_recipe_tpu.ops import flash_causal
    from ml_recipe_tpu.ops.attention import _xla_attention

    if backward == "split":
        monkeypatch.setattr(flash_causal, "_DQ_ROW_BUDGET", 0)
    assert flash_causal.fused_backward(length, d_qk) == (backward == "fused")
    q, k, v, mask, weigh = _causal_case(length, d_qk, d_v, jnp.dtype(dtype))
    kernel = lambda q, k, v: flash_causal.causal_attention(  # noqa: E731
        q, k, v, mask, interpret=True)
    xla = lambda q, k, v: _xla_attention(  # noqa: E731
        q, k, v, mask, causal=True)
    # the reference works in f32 on the operands the kernel was given. f32
    # operands: the kernel's own sums are f32 too. bf16 operands: the kernel
    # rounds the probabilities, ds and the output's cotangent to bf16 before
    # each matmul and each gradient once after it, 2^-9 relative a rounding;
    # over a row's keys they add up to about 2^-8 of the largest gradient
    # (read: 2.6e-3 to 4.0e-3 over these cases), so 2^-6 leaves four times
    # of room and is still a hundred times under a wrong tile's error
    fwd_tol, rel, floor = (1e-5, 2e-5, 1e-6) if dtype == "float32" \
        else (2.0 ** -6, 2.0 ** -6, 0.0)
    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    real = mask[:, :, None, None]
    out = kernel(q, k, v).astype(jnp.float32)
    assert float(jnp.abs((out - xla(*wide)) * real).max()) < fwd_tol
    got = _causal_grads(kernel, q, k, v, weigh)
    want = _causal_grads(xla, *wide, weigh)
    for g, w in zip(got, want):
        assert g.dtype == jnp.dtype(dtype)
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) \
            < rel * float(jnp.abs(w).max()) + floor


def strip_case(monkeypatch, length, window, heads, d_qk, d_v, strip, split):
    """The row strips of the tiles a mask edge cuts (``strip_cuts``) at
    ``strip`` rows give the forward and all three gradients of the XLA
    reference, equal the whole tile's to f32 rounding, and the fused and the
    split backward (``split``) agree to the bit; the causal family where
    ``window`` is None. The second row is padded from inside a block."""
    from ml_recipe_tpu.ops import flash_causal, flash_window
    from ml_recipe_tpu.ops.attention import _xla_attention

    H, H_kv = heads
    rng = np.random.default_rng(length + (window or 0) + strip)
    q = jnp.asarray(rng.normal(size=(2, length, H, d_qk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, length, H_kv, d_qk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, length, H_kv, d_v)), jnp.float32)
    mask = jnp.asarray((np.arange(length)[None, :] < np.array(
        [length, length - length // 5 - 3])[:, None]).astype(np.int32))
    weigh = jnp.asarray(rng.normal(size=(2, length, H, d_v)), jnp.float32) \
        * mask[:, :, None, None]
    blk = flash_causal.pick_block(length)

    def walk(s):
        if window is None:
            return flash_causal.causal_walk(length, blk, s)
        return flash_window.window_walk(length, window, blk, s)

    def out_and_grads(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * weigh), (0, 1, 2))(
                q, k, v)

    def kernel(s):
        return out_and_grads(lambda q, k, v: flash_causal.attend(
            q, k, v, mask, walk(s), interpret=True))

    def xla(q, k, v):
        k, v = (jnp.repeat(x, H // H_kv, axis=2) for x in (k, v))
        return _xla_attention(q, k, v, mask, causal=True, window=window)

    with jax.default_matmul_precision("highest"):
        whole = kernel(blk)
        fused = kernel(strip)
        if split:       # the two-kernel backward: no row's dq fits
            monkeypatch.setattr(flash_causal, "_DQ_ROW_BUDGET", 0)
        got, got_grads = kernel(strip)
        want, want_grads = out_and_grads(xla)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)
    assert float(got) == pytest.approx(float(whole[0]), rel=1e-5, abs=1e-4)
    for g, w, t, f in zip(got_grads, want_grads, whole[1], fused[1]):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max())
        assert float(jnp.abs(g - t).max()) < 2e-6 * float(jnp.abs(t).max())
        assert np.array_equal(np.asarray(g), np.asarray(f))


# (rows, query / key-value heads, d_qk, d_v, strip, split): two and four
# blocks a row, groups of 1, 2 and 8, d_qk != d_v, strips of 128, 256 and
# the whole tile; the window family's are ``test_mellum2.py``'s
CAUSAL_STRIP_CASES = [
    (512, (4, 4), 128, 64, 128, False),
    (768, (8, 1), 64, 64, 128, True),
    (1024, (4, 4), 192, 128, 128, False),
    (1024, (8, 1), 64, 64, 256, True),
    (1024, (4, 2), 128, 64, 512, False),
]


@pytest.mark.parametrize("length, heads, d_qk, d_v, strip, split",
                         CAUSAL_STRIP_CASES, ids=lambda value: str(value))
def test_causal_strips_match_xla_the_whole_tile_and_the_other_backward(
        length, heads, d_qk, d_v, strip, split, monkeypatch):
    strip_case(monkeypatch, length, None, heads, d_qk, d_v, strip, split)


def test_the_causal_backward_is_chosen_from_the_shapes(monkeypatch):
    """One pure function of (L, d_qk): the row's f32 dq, lanes padded, against
    the budget. Where it says fused, the gradient holds ONE backward
    ``pallas_call``, named ``flash_causal_bwd``; the two kernels give the
    same f32 sums in the same order, so the same gradients bit for bit."""
    from ml_recipe_tpu.ops import flash_causal

    assert flash_causal.fused_backward(4096, 192)          # 4 MiB: the cell
    assert flash_causal.fused_backward(8192, 192)          # 8 MiB: the edge
    assert not flash_causal.fused_backward(8192 + 512, 192)
    assert not flash_causal.fused_backward(16384, 192)
    assert flash_causal.fused_backward(16384, 128)         # 128 lanes, not 256
    assert flash_causal.fused_backward(8192, 256) \
        == flash_causal.fused_backward(8192, 192)           # 192 pads to 256

    q, k, v, mask, weigh = _causal_case(768, 192, 128, jnp.float32)
    kernel = lambda q, k, v: flash_causal.causal_attention(  # noqa: E731
        q, k, v, mask, interpret=True)

    def backward_calls():
        text = str(jax.make_jaxpr(
            lambda *qkv: _causal_grads(kernel, *qkv, weigh))(q, k, v))
        return sorted(re.findall(r"name=(flash_causal_bwd\w*)", text))

    assert backward_calls() == ["flash_causal_bwd"]
    fused = _causal_grads(kernel, q, k, v, weigh)
    monkeypatch.setattr(flash_causal, "_DQ_ROW_BUDGET", 0)
    assert backward_calls() == ["flash_causal_bwd_dkv", "flash_causal_bwd_dq"]
    split = _causal_grads(kernel, q, k, v, weigh)
    for one, two in zip(fused, split):
        assert np.array_equal(np.asarray(one), np.asarray(two))


def test_the_dispatcher_picks_the_causal_family_from_the_shapes():
    from ml_recipe_tpu.ops.attention import dot_product_attention

    q = jnp.ones((1, 16, 2, 24))
    v = jnp.ones((1, 16, 2, 16))
    out = dot_product_attention(q, q, v, None, causal=True, impl="auto")
    assert out.shape == (1, 16, 2, 16)
    with pytest.raises(NotImplementedError, match="non-causal"):
        dot_product_attention(q, q, v, None)
    with pytest.raises(NotImplementedError, match="segment ids"):
        dot_product_attention(q, q, v, None, causal=True,
                              segment_ids=jnp.ones((1, 16), jnp.int32))
    with pytest.raises(NotImplementedError, match="dropout"):
        dot_product_attention(q, q, v, None, causal=True, dropout_rate=0.1,
                              dropout_rng=jax.random.key(0))


def test_mechanisms_the_trunk_lacks_raise_by_name():
    mesh = build_mesh("data:2,model:2")
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        mla_moe.unsupported(TINY, mesh=mesh)
    with pytest.raises(NotImplementedError,
                       match="sequence packing.*int8 serving"):
        mla_moe.unsupported(TINY, packing=True, quantize="int8")
    model = QAModel(TINY, quantize="int8")
    with pytest.raises(NotImplementedError, match="int8 serving"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    mla_moe.unsupported(TINY, mesh=build_mesh("data:2"))     # replicated: fine


# -- through the Trainer ---------------------------------------------------------------

class TP:
    loss = "smooth"
    smooth_alpha = 0.01
    focal_alpha = 1
    focal_gamma = 2
    w_start = w_end = w_cls = 1
    w_start_reg = w_end_reg = 1
    lr = 1e-3
    weight_decay = 0.01
    warmup_coef = 0.1
    optimizer = "adam"
    finetune = False
    best_metric = "map"
    best_order = ">"


def make_trainer(tmp_path, *, mesh_spec="data:1", batch_split=2,
                 preset=TINY, **extra):
    tmp_path.mkdir(parents=True, exist_ok=True)
    tokenizer = make_tokenizer(tmp_path)
    rng = np.random.default_rng(0)
    data = dict(tokenizer=tokenizer, max_seq_len=48, max_question_len=12)
    cfg = dataclasses.replace(preset, vocab_size=len(tokenizer))
    mesh = build_mesh(mesh_spec)
    model = QAModel(cfg, attention_impl="xla", mesh=mesh)
    params = QAModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 48), jnp.int32))["params"]
    return Trainer(
        model=model, params=params, loss=build_loss(TP()),
        collate_fun=make_collate_fun(tokenizer, max_seq_len=48),
        trainer_params=TP(),
        train_dataset=DummyDataset(dataset_len=16, rng=rng, **data),
        test_dataset=DummyDataset(dataset_len=8, rng=rng, **data),
        mesh=mesh, n_epochs=1, train_batch_size=8, test_batch_size=8,
        batch_split=batch_split, n_jobs=1, warmup_coef=TP.warmup_coef,
        max_grad_norm=1.0, seed=0, **extra)


def _router_biases(params):
    return {name: np.asarray(layer["mlp"]["router"]["bias"]).copy()
            for name, layer in params["transformer"].items()
            if "router" in layer.get("mlp", {})}


def test_the_selection_bias_stays_put_and_the_counters_reach_the_meters(
        tmp_path):
    seen = {}
    trainer = make_trainer(
        tmp_path, on_train_metrics=lambda meters, step: seen.update(
            {k: float(m()) for k, m in meters.items() if k != "lr"}))
    before = _router_biases(trainer.params)
    kernel_before = np.asarray(
        trainer.params["transformer"]["layer_1"]["mlp"]["router"]["kernel"]
    ).copy()
    trainer.train()
    assert trainer.global_step == 2
    after = _router_biases(trainer.params)
    assert sorted(before) == ["layer_1", "layer_2"]
    for name in before:
        assert np.array_equal(before[name], after[name]), name
    assert not np.array_equal(kernel_before, np.asarray(
        trainer.params["transformer"]["layer_1"]["mlp"]["router"]["kernel"]))
    # 8 rows x 48 tokens x top-2 x 2 expert layers, half of them held
    assert 0.3 < seen["moe_held_share"] < 0.7
    assert seen["moe_held_assignments"] == pytest.approx(
        seen["moe_held_share"] * 8 * 48 * 2 * 2, rel=1e-3)
    assert 1.0 <= seen["moe_load_max_over_mean"] <= 4.0
    assert np.isfinite(seen["loss"])


def test_a_checkpoint_round_trips_the_expert_leaves(tmp_path):
    trainer = make_trainer(tmp_path / "a")
    trainer.train()
    path = tmp_path / "last.ch"
    trainer.save_state_dict(path)
    fresh = make_trainer(tmp_path / "b")
    fresh.load_state_dict(path)
    assert fresh.global_step == trainer.global_step
    for name in ("gate", "up", "down"):
        a, b = (np.asarray(t.params["transformer"]["layer_2"]["mlp"]
                           ["experts"][name]) for t in (trainer, fresh))
        assert a.shape[0] == TINY.experts_held and np.array_equal(a, b), name
    for a, b in zip(jax.tree_util.tree_leaves(trainer.opt_state),
                    jax.tree_util.tree_leaves(fresh.opt_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("batch_split", [1, 2], ids=["gspmd", "island"])
def test_data2_gives_the_one_device_loss(tmp_path, batch_split):
    """The trunk replicated under ``--mesh data:2`` (plain GSPMD at one
    micro-batch a step, the data island at two) against one device."""
    losses = {}
    for mesh_spec in ("data:1", "data:2"):
        seen = []
        trainer = make_trainer(
            tmp_path / mesh_spec.replace(":", ""), mesh_spec=mesh_spec,
            batch_split=batch_split,
            on_train_metrics=lambda meters, step: seen.append(
                {k: float(m()) for k, m in meters.items() if k != "lr"}))
        trainer.train()
        losses[mesh_spec] = seen[-1]
    for key in ("loss", "moe_held_assignments", "moe_held_share"):
        assert losses["data:2"][key] == pytest.approx(
            losses["data:1"][key], rel=2e-4), key


# -- the pre-flight (ROADMAP M9b) ------------------------------------------------------

class _Fits:
    def memory_analysis(self):
        return types.SimpleNamespace(
            temp_size_in_bytes=10, argument_size_in_bytes=10,
            output_size_in_bytes=10, alias_size_in_bytes=10,
            generated_code_size_in_bytes=0)


def test_a_compile_time_resource_exhausted_raises_batch_split(tmp_path):
    trainer = make_trainer(tmp_path, batch_split=2)
    asked = []

    def compile_fn(t):
        asked.append(t.batch_split)
        if t.batch_split < 8:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
                "of memory in memory space hbm. Used 16.64G of 15.75G hbm.")
        return _Fits()

    report = trainer.preflight_train_step(
        None, None, compile_fn=compile_fn, limit_bytes=10 ** 9)
    assert asked == [2, 4, 8]
    assert trainer.batch_split == report["batch_split"] == 8
    assert report["applied"] and report["compile_refused_at"] == [2, 4]


@pytest.mark.parametrize("over_by_copies", [0.5, 1.5], ids=[
    "by_half_a_gradients_copy", "by_more_than_a_copy"])
def test_an_analysis_over_the_limit_raises_batch_split(
        tmp_path, over_by_copies):
    """The step accumulates per tensor and holds no second f32 copy of the
    gradient that could be given up: over the limit by less than such a copy
    or by more, the answer is a smaller micro-batch."""
    trainer = make_trainer(tmp_path, batch_split=2, hbm_preflight=True)
    limit = 10 ** 9
    asked = []

    def compile_fn(t):
        asked.append(t.batch_split)
        copy = t._preflight_pipe_fields()["param_bytes"]
        need = limit + int(over_by_copies * copy) if len(asked) == 1 else 30
        return types.SimpleNamespace(memory_analysis=lambda: types.SimpleNamespace(
            temp_size_in_bytes=need, argument_size_in_bytes=0,
            output_size_in_bytes=0, alias_size_in_bytes=0))

    report = trainer.preflight_train_step(
        None, None, compile_fn=compile_fn, limit_bytes=limit)
    assert asked == [2, 4]
    assert trainer.batch_split == report["batch_split"] == 4
    assert report["applied"]


@pytest.mark.parametrize("preset", ["joyai-tiny", "bert-tiny"])
def test_the_preflight_reports_the_kernel_forms_its_step_traced(
        tmp_path, preset):
    """``kernel_calls``: the kernel families' calls the checked step traced,
    by form. On the CPU the expert layers' grouped matmuls take
    ``ragged_dot`` and their token-side walks the XLA form, and no causal
    kernel runs (no ``attn_tiles``); a trunk with no such family has no
    key."""
    trainer = make_trainer(tmp_path, batch_split=2,
                           preset=MODEL_PRESETS[preset])
    inputs, labels = next(iter(trainer.train_dataloader))
    report = trainer.preflight_train_step(inputs, labels,
                                          limit_bytes=10 ** 12)
    if preset == "bert-tiny":
        assert "kernel_calls" not in report
        return
    # an expert layer traces 6 grouped matmuls and 7 token-side walks
    # (tests/test_grouped_matmul.py, tests/test_token_rows.py count them);
    # the micro-batch loop's body is traced once
    experts = TINY.num_layers - TINY.first_k_dense_replace
    assert report["kernel_calls"] == {
        "grouped_matmul": {"ragged_dot": 6 * experts},
        "token_rows": {"xla": 7 * experts}}


def test_a_compile_error_that_is_no_oom_still_propagates(tmp_path):
    trainer = make_trainer(tmp_path, batch_split=2)

    def compile_fn(t):
        raise RuntimeError("INVALID_ARGUMENT: something else")

    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        trainer.preflight_train_step(None, None, compile_fn=compile_fn,
                                     limit_bytes=10 ** 9)


def test_an_oom_at_the_last_split_propagates(tmp_path):
    trainer = make_trainer(tmp_path, batch_split=8)

    def compile_fn(t):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        trainer.preflight_train_step(None, None, compile_fn=compile_fn,
                                     limit_bytes=10 ** 9)

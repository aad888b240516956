"""The ``mellum`` trunk (sliding-window attention layers, three to every
full-attention layer under YaRN, a softmax router over routed experts in
every layer) at the tiny preset on the CPU: the window kernels in interpret
mode and the XLA band against an explicit mask, YaRN's range and factor at
the published numbers, the softmax router by hand, the system against the
in-repo plain reference (``perfbench/harness/reference_mellum2.py``) for
logits, loss and gradients, the four shares of an EP4 deployment against the
uncut layer, the older presets' step programs against their recorded
digests, and one step through the ``Trainer``.
"""

import dataclasses
import hashlib
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from ml_recipe_tpu.losses import build_loss  # noqa: E402
from ml_recipe_tpu.models import MODEL_PRESETS, QAModel  # noqa: E402
from ml_recipe_tpu.models import mla_moe  # noqa: E402
from ml_recipe_tpu.ops import flash_causal, flash_window  # noqa: E402
from ml_recipe_tpu.ops.attention import (_xla_attention,  # noqa: E402
                                         dot_product_attention)
from ml_recipe_tpu.parallel import build_mesh  # noqa: E402
from perfbench.harness import checks, reference_mellum2  # noqa: E402

from test_dp_equivalence import _step_args  # noqa: E402
from test_mla_moe import (_layer_params, make_trainer,  # noqa: E402
                          strip_case)

TINY = MODEL_PRESETS["mellum2-tiny"]
FULL = MODEL_PRESETS["mellum2-12b-a2.5b-ep4"]
L = 64


def ref_cfg(cfg=TINY, **over):
    """The configuration file's keys for a ``DecoderConfig``."""
    plain = {"rope_type": "default", "rope_theta": cfg.rope_theta}
    out = {
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "layer_types": list(cfg.layer_types),
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "sliding_attention": plain,
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.yarn_factor,
                "original_max_position_embeddings":
                    cfg.yarn_original_positions,
                "beta_fast": cfg.yarn_beta_fast,
                "beta_slow": cfg.yarn_beta_slow,
                "attention_factor": cfg.yarn_attention_factor,
            } if cfg.yarn_factor else plain},
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "experts_held": {"first": cfg.experts_first,
                         "count": cfg.experts_held,
                         "of": cfg.n_routed_experts},
    }
    out.update(over)
    return out


# -- the window: kernels, the XLA band, the dispatcher --------------------------------

def explicit_band(q, k, v, mask, window):
    """``softmax(q k^T / sqrt(d) + M) v`` with ``M`` written element by
    element from ``i - j``, in float64 on the host."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    B, length, H, d = q.shape
    group = H // k.shape[2]
    out = np.zeros((B, length, H, v.shape[-1]))
    for b in range(B):
        for h in range(H):
            s = q[b, :, h] @ k[b, :, h // group].T / np.sqrt(d)
            for i in range(length):
                allowed = np.asarray(
                    [0 <= i - j < window and mask[b, j] > 0
                     for j in range(length)])
                if not allowed.any():
                    continue
                e = np.where(allowed, np.exp(s[i] - s[i][allowed].max()), 0.0)
                out[b, i, h] = (e / e.sum()) @ v[b, :, h // group]
    return out


def _operands(length, H=4, H_kv=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, length, H, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, length, H_kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, length, H_kv, d)), dtype)
    mask = jnp.asarray((np.arange(length)[None, :] < np.array(
        [length, length - length // 5 - 3])[:, None]).astype(np.int32))
    weigh = jnp.asarray(rng.normal(size=(2, length, H, d)), jnp.float32) \
        * mask[:, :, None, None]
    return q, k, v, mask, weigh


def _xla_band(q, k, v, mask, window):
    group = q.shape[2] // k.shape[2]
    return _xla_attention(q, jnp.repeat(k, group, axis=2),
                          jnp.repeat(v, group, axis=2), mask, causal=True,
                          window=window)


@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_the_xla_band_is_the_explicit_mask(window):
    q, k, v, mask, _ = _operands(24, d=8)
    real = np.asarray(mask, bool)[:, :, None, None]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_xla_band(q, k, v, mask, window))
        ref = np.asarray(reference_mellum2.attention_core(
            q, k, v, mask, window, q_block=8))
    want = explicit_band(q, k, v, np.asarray(mask), window)
    assert np.abs((got - want) * real).max() < 1e-5
    assert np.abs((ref - want) * real).max() < 1e-5


# (rows, window, split): windows that are and are not multiples of the block
# edge (256 at 512 and 768, 512 at 2,048), one shorter than a block (the
# diagonal tile masks both sides), one that reaches two blocks back
WINDOW_CASES = [(512, 256, False), (512, 200, False), (512, 257, False),
                (768, 300, True), (768, 100, False), (2048, 700, False),
                (2048, 1024, True)]


@pytest.mark.parametrize("length, window, split", WINDOW_CASES)
def test_window_kernels_match_the_xla_band_forward_and_backward(
        length, window, split, monkeypatch):
    q, k, v, mask, weigh = _operands(length, seed=length + window)
    if split:       # the two-kernel backward: no row's dq fits the budget
        monkeypatch.setattr(flash_causal, "_DQ_ROW_BUDGET", 0)

    def weighed(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * weigh), (0, 1, 2))

    with jax.default_matmul_precision("highest"):
        got, got_grads = weighed(lambda q, k, v: flash_window.window_attention(
            q, k, v, mask, window=window, interpret=True))(q, k, v)
        want, want_grads = weighed(
            lambda q, k, v: _xla_band(q, k, v, mask, window))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max())


@pytest.mark.parametrize("off", [-1, 1])
def test_a_window_one_key_off_differs_beyond_the_tolerance(off):
    length, window = 512, 200
    q, k, v, mask, _ = _operands(length, seed=9)
    real = np.asarray(mask, bool)[:, :, None, None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_xla_band(q, k, v, mask, window))
        exact, other = (np.asarray(flash_window.window_attention(
            q, k, v, mask, window=w, interpret=True))
            for w in (window, window + off))
    assert np.abs((exact - want) * real).max() < 1e-5
    assert np.abs((other - want) * real).max() > 1e-2


def test_the_tables_keep_the_blocks_the_band_touches():
    # 8,192 under 1,024 at a block edge of 512: the diagonal, one whole block
    # and the far edge, whatever the row's length
    assert flash_causal.pick_block(8192) == 512
    assert (flash_window.reach(1024, 512), flash_window.edge(1024, 512)) \
        == (2, 2)
    assert flash_window.block_pairs(8192, 1024) == (45, 136)
    qi, ki = flash_window.pairs(16, 2, k_outer=False)
    assert list(ki[qi == 7]) == [5, 6, 7] and list(ki[qi == 1]) == [0, 1]
    q_outer = {tuple(p) for p in flash_window.pairs(16, 2, k_outer=False).T}
    k_outer = flash_window.pairs(16, 2, k_outer=True)
    assert {tuple(p) for p in k_outer.T} == q_outer
    assert list(k_outer[1]) == sorted(k_outer[1])       # k outermost
    # a window no longer than a block needs the far mask on the diagonal too
    assert flash_window.edge(511, 512) == 0 and flash_window.edge(512, 512) == 1
    assert flash_window.block_pairs(8, 1024) == (0, 0)  # no block edge


# (rows, window, query / key-value heads, d_qk, d_v, strip, split): windows
# at the block edge (the far tile's band is 0), under it (the diagonal cut on
# both sides), at mellum2's geometry, past it (two far distances), and one
# whose far tile's lower strips reach no key; the causal cases are
# ``test_mla_moe.py``'s (``strip_case``)
WINDOW_STRIP_CASES = [
    (1024, 512, (8, 1), 128, 64, 128, False),
    (1024, 100, (4, 4), 64, 64, 128, True),
    (2048, 1024, (8, 1), 64, 64, 128, False),
    (2048, 700, (4, 2), 128, 64, 256, True),
    (2048, 514, (4, 4), 64, 64, 128, False),
    (768, 300, (4, 2), 64, 64, 256, False),
]


@pytest.mark.parametrize(
    "length, window, heads, d_qk, d_v, strip, split", WINDOW_STRIP_CASES,
    ids=lambda value: str(value))
def test_window_strips_match_xla_the_whole_tile_and_the_other_backward(
        length, window, heads, d_qk, d_v, strip, split, monkeypatch):
    strip_case(monkeypatch, length, window, heads, d_qk, d_v, strip, split)


@pytest.mark.parametrize("length, window, d_qk, d_v, strip, area", [
    (8192, 1024, 128, 128, 128, (540, 720)),    # mellum2's window calls
    (8192, 1024, 128, 128, 256, (600, 720)),
    (8192, 1024, 128, 128, 512, (720, 720)),
    (8192, None, 128, 128, 128, (2080, 2176)),  # its full layer; olmo's
    (4096, None, 192, 128, 128, (528, 576)),    # joyai's
    (8192, None, 64, 64, 128, (2080, 2176)),    # lfm2's
    (64, 1024, 16, 16, 128, (0, 0)),            # no block edge
])
def test_tile_area_counts_the_sub_tiles_a_grid_computes_and_walks(
        length, window, d_qk, d_v, strip, area):
    blk = flash_causal.pick_block(length)
    assert flash_window.tile_area(length, window, blk, strip) == area
    # mellum2's window calls at strips of 128: 33.75 of 45 tiles a row/head
    if area[1] == 720:
        assert area[1] / 16 == flash_window.block_pairs(length, window)[0]
    # what an attention layer sows for calls at the kernels' own strip
    stats = mla_moe._tile_stats(length, window, d_qk, d_v, calls=3)
    own = flash_window.tile_area(
        length, window, blk, flash_causal.strip_height(blk)) if blk else (0, 0)
    assert (float(stats["attn_tile_area_computed"]),
            float(stats["attn_tile_area_walked"])) == (3 * own[0], 3 * own[1])


def test_the_dispatcher_takes_the_window_and_none_is_the_causal_program():
    q, k, v, mask, _ = _operands(32, d=8)
    with jax.default_matmul_precision("highest"):
        banded = dot_product_attention(q, k, v, mask, impl="xla", causal=True,
                                       window=5)
        causal = dot_product_attention(q, k, v, mask, impl="xla", causal=True)
        long = dot_product_attention(q, k, v, mask, impl="xla", causal=True,
                                     window=32)
        want = _xla_band(q, k, v, mask, 5)
    assert np.allclose(banded, want, atol=1e-6)
    assert np.array_equal(np.asarray(long), np.asarray(causal))
    assert float(jnp.abs(banded - causal).max()) > 1e-2
    text = lambda **how: str(jax.make_jaxpr(  # noqa: E731
        lambda q, k, v: dot_product_attention(
            q, k, v, mask, impl="xla", causal=True, **how))(q, k, v))
    assert text() == text(window=None) == text(window=32)
    with pytest.raises(ValueError, match="causal=True"):
        dot_product_attention(q, k, v, mask, impl="xla", window=5)
    with pytest.raises(NotImplementedError, match="segment ids"):
        dot_product_attention(q, k, v, mask, impl="xla", causal=True,
                              window=5, segment_ids=mask)
    with pytest.raises(ValueError, match="pallas"):
        dot_product_attention(q, k, v, mask, impl="pallas", causal=True,
                              window=5)        # 32 rows: no block edge


# the step programs of the older trunks' tiny presets (StableHLO, sha256[:16],
# CPU lowering of ``make_trainer``'s step, one chip): what PRs 32-36 recorded
# and the parent of PR 37 gives; the four routed ones moved on purpose at PR
# 38, which gave the expert layers a sixth counter, ``moe_row_tile_fill``
# (``7e5f373c8e11e10f`` / ``45775d4ad6fd7f18`` / ``df0673316961ddb5`` /
# ``b1e55c2b53174ee2`` before it; on the CPU the grouped matmuls are still
# ``ragged_dot``); all six moved on purpose when every attention layer began
# to report its kernels' sub-tiles (``mla_moe.TILE_STAT_KEYS``; with those
# two counters left out the step is still ``247ee4b53014ca16`` /
# ``68a08c0f99add3cb`` / ``def2e63e82defaaf`` / ``cb8cda58d4f78be0`` /
# ``cde6697313bf175c`` / ``7e2c3132857bba33``)
RECORDED = {
    ("joyai-tiny", 1): "44152c22ebec60b8", ("joyai-tiny", 2): "ee4f3b7e3361a32b",
    ("lfm2-tiny", 1): "89bd71999ec3952f", ("lfm2-tiny", 2): "090629d54469f7b7",
    ("olmo-hybrid-tiny", 1): "ec24d7c1eb603bc9",
    ("olmo-hybrid-tiny", 2): "8c964c583770c464",
}


@pytest.mark.parametrize("preset, split", sorted(RECORDED))
def test_an_older_presets_step_is_the_program_it_was(tmp_path, preset, split):
    trainer = make_trainer(tmp_path, batch_split=split,
                           preset=MODEL_PRESETS[preset])
    text = trainer._build_train_step().lower(*_step_args(trainer)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == RECORDED[preset, split]


@pytest.mark.parametrize("preset", [
    "joyai-llm-flash-ep16", "joyai-tiny", "lfm2-8b-a1b-ep4", "lfm2-tiny",
    "olmo-hybrid-7b-pp8", "olmo-hybrid-tiny"])
def test_the_older_presets_keep_their_defaults(preset):
    cfg = MODEL_PRESETS[preset]
    assert (cfg.sliding_window, cfg.yarn_factor, cfg.scoring_func,
            cfg.windows, cfg.embedding_range) == (0, 0.0, "sigmoid", False,
                                                  0.0)
    assert "attn_window_block_pairs" not in mla_moe.step_stat_keys(cfg)


# -- the rotation and the router -------------------------------------------------------------

def test_yarn_blends_between_pairs_18_and_35_at_the_published_numbers():
    assert mla_moe.yarn_range(FULL, 128) == (18, 35)
    own, one = mla_moe.pair_frequencies(FULL, "sliding_attention", 128)
    blended, factor = mla_moe.pair_frequencies(FULL, "full_attention", 128)
    assert one == 1.0 and factor == 1.2772588722239782
    assert factor == pytest.approx(0.1 * np.log(16.0) + 1.0, rel=1e-12)
    own, blended = np.asarray(own), np.asarray(blended)
    assert np.allclose(own, 500000.0 ** (-np.arange(64) / 64.0), rtol=1e-5)
    assert np.array_equal(blended[:19], own[:19])
    assert np.allclose(blended[35:], own[35:] / 16.0, rtol=1e-6)
    ramp = (np.arange(64) - 18) / 17.0
    assert np.allclose(blended[19:35], ((1 - ramp) * own + ramp * own / 16)[
        19:35], rtol=1e-5)
    want, want_factor = reference_mellum2.rotation(
        ref_cfg(FULL), "full_attention", 128)
    assert np.allclose(blended, want, rtol=1e-6) and want_factor == factor
    # without the key the factor is YaRN's own 0.1 ln(factor) + 1
    bare = dataclasses.replace(FULL, yarn_attention_factor=0.0)
    assert mla_moe.pair_frequencies(bare, "full_attention", 128)[1] \
        == pytest.approx(factor, rel=1e-12)


def test_the_rotation_scales_cos_and_sin_and_a_logit_by_the_square():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 2, 16)),
                    jnp.float32)
    positions = jnp.arange(6)
    frequencies, factor = mla_moe.pair_frequencies(TINY, "full_attention", 16)
    plain = mla_moe.rotate_half_split(x, positions, frequencies)
    scaled = mla_moe.rotate_half_split(x, positions, frequencies, factor)
    assert np.allclose(scaled, plain * factor, rtol=1e-6)
    want = reference_mellum2.rope_half_split(x, frequencies, factor)
    assert np.allclose(scaled, want, atol=1e-6)
    by_theta = mla_moe.rotate_half_split(x, positions, TINY.rope_theta)
    own, _ = mla_moe.pair_frequencies(TINY, "sliding_attention", 16)
    assert np.array_equal(
        np.asarray(by_theta),
        np.asarray(mla_moe.rotate_half_split(x, positions, own)))


def _plain_router(x, kernel, K):
    """The published form: softmax over all experts, top-k, renormalised."""
    scores = jax.nn.softmax(jnp.dot(
        x, kernel, precision=jax.lax.Precision.HIGHEST), axis=-1)
    top, chosen = jax.lax.top_k(scores, K)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def test_the_softmax_router_has_no_bias_and_its_weights_sum_to_one():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    router = mla_moe.Router(TINY)
    params = router.init(jax.random.key(0), x)["params"]
    assert set(params) == {"kernel"}
    kernel = params["kernel"] * 20.0
    chosen, weights = router.apply({"params": {"kernel": kernel}}, x)
    want_chosen, want_weights = _plain_router(x, kernel, 2)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
    assert np.allclose(weights, want_weights, atol=1e-6)
    assert np.allclose(jnp.sum(weights, axis=-1), 1.0, atol=1e-6)
    weigh = jnp.asarray(np.random.default_rng(2).normal(size=(40, 2)),
                        jnp.float32)
    got = jax.grad(lambda k: jnp.sum(
        router.apply({"params": {"kernel": k}}, x)[1] * weigh))(kernel)
    want = jax.grad(lambda k: jnp.sum(
        _plain_router(x, k, 2)[1] * weigh))(kernel)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    # the sigmoid router keeps its bias
    assert "bias" in mla_moe.Router(MODEL_PRESETS["lfm2-tiny"]).init(
        jax.random.key(0), x)["params"]


def test_the_embedding_is_seeded_at_its_own_range():
    ids = jnp.zeros((1, 8), jnp.int32)
    for preset, want in (("mellum2-tiny", 1.0), ("lfm2-tiny", 0.02)):
        table = QAModel(MODEL_PRESETS[preset]).init(jax.random.key(0), ids)[
            "params"]["transformer"]["word_embeddings"]["embedding"]
        assert float(jnp.std(table)) == pytest.approx(want, rel=0.02), preset


def test_the_configuration_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(TINY, sliding_window=0)
    with pytest.raises(ValueError, match="scoring_func"):
        dataclasses.replace(TINY, scoring_func="tanh")
    with pytest.raises(ValueError, match="yarn_original_positions"):
        dataclasses.replace(TINY, yarn_original_positions=0)
    with pytest.raises(ValueError, match="sliding_attention"):
        dataclasses.replace(TINY, layer_types=("window",) * 4)


# -- the system against the reference ------------------------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    """Model, seeded weights moved off their initial scale (at 0.02 the
    router's logits are hundredths and every expert weighs an eighth), ragged
    rows longer than the window and labels."""
    model = QAModel(TINY, dtype=jnp.float32, attention_impl="xla")
    inputs, labels = checks.seeded_rows(5, TINY.vocab_size, L, [L, 50, 27, 9])
    params = model.init(jax.random.key(1), inputs["input_ids"])["params"]

    def widen(path, x):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "kernel" and "router" in names:
            return x * 30.0
        if names[-1] in ("gate", "up", "down", "kernel", "embedding"):
            return x * 4.0
        if names[-1] == "scale" and names[-2] in ("q_layer_norm",
                                                  "k_layer_norm"):
            return x * 2.0      # logits of a few units: the band shows
        return x

    params = jax.tree_util.tree_map_with_path(widen, params)
    for key, name in zip(jax.random.split(jax.random.key(2), 4), (
            "position_outputs", "classifier", "reg_start", "reg_end")):
        params[name]["bias"] = 0.1 * jax.random.normal(
            key, params[name]["bias"].shape)
    return model, jax.device_get(params), inputs, labels


def system_outputs(model, params, inputs):
    with jax.default_matmul_precision("highest"):
        return model.apply({"params": params}, **inputs, deterministic=True)


def test_system_matches_the_reference_logits_loss_and_gradients(seeded):
    model, params, inputs, labels = seeded
    got = system_outputs(model, params, inputs)
    want, own = reference_mellum2.forward(params, ref_cfg(), **inputs,
                                          q_block=16)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert max(errors.values()) < 5e-5, errors      # float32 against float32
    assert len(own["chosen"]) == len(own["attention"]) == TINY.num_layers
    loss_fn = build_loss(types.SimpleNamespace(loss="smooth",
                                               smooth_alpha=0.01))
    device_labels = {k: jnp.asarray(v) for k, v in labels.items()}

    def system_loss(p):
        return loss_fn(system_outputs(model, p, inputs), device_labels)[0]

    def reference_loss(p):
        preds, _ = reference_mellum2.forward(p, ref_cfg(), **inputs,
                                             q_block=16)
        return reference_mellum2.loss(preds, labels, smooth_alpha=0.01)

    loss, grads = jax.value_and_grad(system_loss)(params)
    want_loss, want_grads = jax.value_and_grad(reference_loss)(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    want_flat = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    seen = set()
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = want_flat[path]
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name               # every leaf takes part
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 5e-7, name
        seen.add(name.split("/")[2] if name.startswith("transformer/layer")
                 else name.split("/")[0])
    assert {"attention", "mlp", "input_layer_norm"} <= seen


def _no_window(cfg):
    return dict(cfg, sliding_window=10 ** 6)


def _no_yarn(cfg):
    rope = cfg["rope_parameters"]
    return dict(cfg, rope_parameters=dict(
        rope, full_attention=rope["sliding_attention"]))


def _no_yarn_factor(cfg):
    rope = cfg["rope_parameters"]
    return dict(cfg, rope_parameters=dict(rope, full_attention=dict(
        rope["full_attention"], attention_factor=1.0)))


def _window_off_by_one(cfg):
    return dict(cfg, sliding_window=cfg["sliding_window"] + 1)


def _no_renormalisation(cfg):
    return dict(cfg, norm_topk_prob=False)


@pytest.mark.parametrize("drop", [
    _no_window, _no_yarn, _no_yarn_factor, _window_off_by_one,
    _no_renormalisation])
def test_a_dropped_term_shows_against_the_reference(seeded, drop):
    """The reference with one term of the equations changed no longer agrees
    with the system: the seeded fixture can tell each of them."""
    model, params, inputs, _ = seeded
    got = system_outputs(model, params, inputs)
    want, _ = reference_mellum2.forward(params, drop(ref_cfg()), **inputs,
                                        q_block=16)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert max(errors.values()) > 1e-3, errors


def test_both_kinds_of_layer_sow_what_their_cores_read_and_the_pair_counters(
        seeded):
    model, params, inputs, _ = seeded
    long = {k: jnp.tile(v, (1, 6)) for k, v in inputs.items()}   # 384 tokens
    _, stats = model.apply_with_stats({"params": params}, **long)
    # three window layers x 4 rows x 4 heads x (forward + fused backward)
    # x 5 of the 6 pairs three blocks of 128 have
    assert float(stats["attn_window_block_pairs"]) == 3 * 4 * 4 * 2 * 5
    assert float(stats["attn_causal_block_pairs"]) == 3 * 4 * 4 * 2 * 6
    assert set(mla_moe.step_stat_keys(TINY)) >= {
        "moe_held_assignments", "moe_load_max_over_mean",
        "attn_window_block_pairs", "attn_causal_block_pairs",
        "attn_tile_area_computed", "attn_tile_area_walked"}
    _, sown = model.apply({"params": params}, **inputs,
                          mutable=[mla_moe.ROUTING])
    layers = sown[mla_moe.ROUTING]["transformer"]
    for i, kind in enumerate(TINY.layer_types):
        q, k, v = layers[f"layer_{i}"]["attention"]["attention_input"][0]
        assert (q.shape, k.shape) == ((4, L, 4, 16), (4, L, 2, 16))
        stats = layers[f"layer_{i}"]["attention"]["stats"][0]
        assert ("attn_window_block_pairs" in stats) \
            == (kind == "sliding_attention")
        # heads of 16: no kernel takes them, so no sub-tile is walked
        assert (float(stats["attn_tile_area_computed"]),
                float(stats["attn_tile_area_walked"])) == (0.0, 0.0)


# -- the share ---------------------------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each (EP4 of the tiny preset's eight)
    against the reference holding all eight: with no shared expert the parts
    simply add (guide, section 4)."""
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=8)
    _, params, x = _layer_params(whole, jax.random.key(3))
    params["router"]["kernel"] = params["router"]["kernel"] * 8.0
    with jax.default_matmul_precision("highest"):
        want = reference_mellum2.expert_layer(params, ref_cfg(whole), x)[0]
    total = jnp.zeros_like(want)
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(TINY, experts_first=first, experts_held=2)
        held = dict(params, experts=jax.tree_util.tree_map(
            lambda w: w[first:first + 2], params["experts"]))
        with jax.default_matmul_precision("highest"):
            part = mla_moe.ExpertLayer(share, jnp.float32).apply(
                {"params": held}, x)
            alone = reference_mellum2.expert_layer(
                held, ref_cfg(share), x)[0]
        assert float(jnp.abs(part - alone).max()) < 1e-5
        total = total + part
    assert float(jnp.abs(total - want).max()) < 2e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_the_presets_hold_the_published_widths_and_the_parameter_count():
    assert (FULL.hidden_size, FULL.num_heads, FULL.num_kv_heads,
            FULL.head_dim, FULL.moe_intermediate_size, FULL.n_routed_experts,
            FULL.num_experts_per_tok, FULL.sliding_window) == (
        2304, 32, 4, 128, 896, 64, 8, 1024)
    assert not FULL.first_k_dense_replace and FULL.routes and FULL.windows
    shapes = jax.eval_shape(
        lambda key: QAModel(FULL).init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"]
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    layer = shapes["transformer"]["layer_0"]
    assert count(layer["attention"]) == 21_233_664 + 256
    assert count(layer["mlp"]["router"]) == 147_456
    assert count(layer["mlp"]["experts"]) == 16 * 6_193_152
    assert count(layer) == 120_476_416
    assert count(shapes["transformer"]["word_embeddings"]) == 56_623_104
    assert count(shapes) == pytest.approx(538.55e6, rel=2e-4)


def test_mechanisms_the_trunk_lacks_raise_by_name():
    with pytest.raises(
            NotImplementedError,
            match="mellum trunk .full_attention / sliding_attention.*pipe"):
        mla_moe.unsupported(TINY, mesh=build_mesh("data:2,pipe:2"))
    with pytest.raises(NotImplementedError,
                       match="sequence packing.*int8 serving"):
        mla_moe.unsupported(TINY, packing=True, quantize="int8")
    mla_moe.unsupported(TINY, mesh=build_mesh("data:2"))     # replicated: fine


def test_steps_through_the_trainer_and_data2_gives_the_one_device_loss(
        tmp_path):
    """``QAModel`` -> ``Trainer`` -> ``build_step`` on the tiny preset (rows
    of 48 under a window of 40), on one device and replicated under ``--mesh
    data:2`` (the data island): the routing and the pair counters reach the
    meters, both kinds of layer and the router move, and both meshes give one
    loss."""
    last = {}
    for mesh_spec in ("data:1", "data:2"):
        seen = []
        trainer = make_trainer(
            tmp_path / mesh_spec.replace(":", ""), mesh_spec=mesh_spec,
            preset=TINY, on_train_metrics=lambda meters, step: seen.append(
                {k: float(m()) for k, m in meters.items() if k != "lr"}))
        before = jax.device_get(trainer.params["transformer"])
        trainer.train()
        after = jax.device_get(trainer.params["transformer"])
        assert trainer.global_step == 2 and np.isfinite(seen[-1]["loss"])
        moved = lambda *path: not np.array_equal(  # noqa: E731
            *(np.asarray(_at(t, path)) for t in (before, after)))
        assert moved("layer_0", "attention", "k", "kernel")
        assert moved("layer_3", "attention", "q_layer_norm", "scale")
        assert moved("layer_2", "mlp", "experts", "down")
        assert moved("layer_1", "mlp", "router", "kernel")
        # 8 rows x 48 tokens x top-2 x 4 expert layers, half of them held
        assert 0.3 < seen[-1]["moe_held_share"] < 0.7
        assert seen[-1]["moe_held_assignments"] == pytest.approx(
            seen[-1]["moe_held_share"] * 8 * 48 * 2 * 4, rel=1e-3)
        # 48 tokens: no block edge divides them, so no table is walked
        assert seen[-1]["attn_window_block_pairs"] == 0.0
        last[mesh_spec] = seen[-1]
    for key in ("loss", "moe_held_assignments", "moe_held_share"):
        assert last["data:2"][key] == pytest.approx(
            last["data:1"][key], rel=2e-4), key


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def test_the_pair_counters_reach_the_registry():
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    telemetry = TrainTelemetry()
    telemetry.observe_scalars({"loss": 1.0, "attn_window_block_pairs": 240.0,
                               "attn_causal_block_pairs": 288.0,
                               "attn_tile_area_computed": 540.0,
                               "attn_tile_area_walked": 720.0,
                               "moe_held_assignments": 12.0})
    registry = telemetry.registry
    assert registry.get("train_attn_window_block_pairs").quantile(0.5) == 240
    assert registry.get("train_attn_causal_block_pairs").quantile(0.5) == 288
    assert registry.get("train_attn_tile_area_computed").quantile(0.5) == 540
    assert registry.get("train_attn_tile_area_walked").quantile(0.5) == 720
    assert registry.get("train_moe_held_assignments").count == 1


# -- the readings behind the comparison's limits, at the tiny size -----------------------------

@pytest.fixture(scope="module")
def verdicts():
    """``scripts/mellum2_tolerance_readings.py --rehearse``: the script's own
    path (the cell's tiny configuration, bf16) through ``compare``, once for
    the system and once for each lowered control."""
    import contextlib
    import importlib.util
    import io
    import json

    spec = importlib.util.spec_from_file_location(
        "mellum2_tolerance_readings",
        REPO / "scripts" / "mellum2_tolerance_readings.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert module.main(["--rehearse", "--seeds", "3700000913"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["seed"] == 3700000913
    return line["verdicts"]


@pytest.mark.parametrize("control, caught_by", [
    ("system", None),
    ("no_window", "attention_on_one_input"),
    ("no_yarn", "attention_inputs_along_the_trajectory"),
    # see below: at this size the XLA path reads as a bf16 softmax does
    ("bf16_softmax", ""),
    ("bf16_router", "router_on_one_state"),
    ("float8_matmuls", "logits"),
    ("bf16_partial_sums", ""),
])
def test_the_comparison_passes_the_system_and_names_what_catches_a_control(
        verdicts, control, caught_by):
    """On the CPU the rehearsal's bf16 system takes XLA attention, whose
    ``einsum`` returns the LOGITS in its inputs' bf16: over a window of 40
    keys that reads 0.0032-0.0043 in part (d), over the limit of 0.003 the
    chip's readings set between the kernels (0.0017-0.0021: f32 logits) and a
    bf16 softmax (0.0048). So part (d) is read here, not judged, for the
    system and the bf16 softmax alike; every other part is judged."""
    verdict = verdicts[control]
    assert set(verdict) >= {"ok", "failed_parts", "routing", "attention",
                            "logit_abs_err", "logit_tol"}
    layers = verdict["attention"]["layers"]
    assert len(layers) == 4
    if caught_by is None:
        assert set(verdict["failed_parts"]) <= {"attention_on_one_input"}
        assert all(r["error_rms_share"] < 0.006 for r in layers)
    elif caught_by:
        assert not verdict["ok"] and caught_by in verdict["failed_parts"]
    if control == "no_window":      # the sliding layers read it, the full
        #                             layer's own core does not
        assert all(r["error_rms_share"] > 0.1 for r in layers[:3])
        assert layers[3]["error_rms_share"] < 0.01
    if control == "no_yarn":        # its core is handed the q and k it made
        assert max(r["error_rms_share"] for r in layers) < 0.006
        assert max(verdict["attention"]["input_drift"][3].values()) > 0.2
        assert max(verdict["attention"]["input_drift"][2].values()) < 0.05

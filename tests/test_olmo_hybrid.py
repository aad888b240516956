"""The ``olmo_hybrid`` trunk (gated delta-rule linear-attention layers, three
to every full-attention layer, reordered norms, dense FFNs) at the tiny preset
on the CPU: the chunked delta rule against the token-by-token recurrence
(output and every gradient, chunks that do and do not divide the row, ``d_k !=
d_v``, a negative eigenvalue by hand), the convolution + SiLU against an
explicit loop, the gated norm, the reordered norms, the whole-width q/k norm
and "no rotation" by hand, what the custom backward keeps, the system against
the in-repo plain reference (``perfbench/harness/reference_olmo_hybrid.py``)
for logits, loss and gradients, each dropped term against the benchmark's
tolerance, the mechanisms the operator lacks, the counters of a trunk with no
expert layer, and one step through the ``Trainer`` with ``remat`` on and off.
"""

import dataclasses
import inspect
import sys
import types
import zlib
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
from ml_recipe_tpu.ops import gated_delta  # noqa: E402
from ml_recipe_tpu.ops.gated_delta import gated_delta_rule  # noqa: E402
from ml_recipe_tpu.ops.short_conv import causal_conv_silu  # noqa: E402
from ml_recipe_tpu.parallel import build_mesh  # noqa: E402
from perfbench.harness import checks, reference_olmo_hybrid  # noqa: E402

from test_mla_moe import make_trainer  # noqa: E402

TINY = MODEL_PRESETS["olmo-hybrid-tiny"]
L = 48


def ref_cfg(cfg=TINY, **over):
    """The configuration file's keys for a ``DecoderConfig``."""
    out = {
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads or cfg.num_heads,
        "layer_types": list(cfg.layer_types),
        "linear_num_key_heads": cfg.linear_num_heads,
        "linear_num_value_heads": cfg.linear_num_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
        "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
    }
    out.update(over)
    return out


# -- the operator -----------------------------------------------------------------------

def _operands(seed=0, B=2, length=50, H=3, d_k=8, d_v=16, dtype=jnp.float32):
    """As a layer makes them: unit ``q`` and ``k``, ``beta`` in (0, 2),
    ``g <= 0`` from slow to fast decay."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (jnp.asarray(unit(rng.normal(size=(B, length, H, d_k))), dtype),
            jnp.asarray(unit(rng.normal(size=(B, length, H, d_k))), dtype),
            jnp.asarray(rng.normal(size=(B, length, H, d_v)), dtype),
            jnp.asarray(-np.exp(rng.normal(size=(B, length, H)) - 2),
                        jnp.float32),
            jnp.asarray(2 / (1 + np.exp(-rng.normal(size=(B, length, H)))),
                        jnp.float32))


def chunked_rule(chunk):
    """The operator at a chunk size of the test's (the program's is
    ``gated_delta.CHUNK``)."""
    return lambda *ops: gated_delta._gated_delta(*ops, chunk)


def loop_rule(q, k, v, g, beta):
    """The recurrence in float64, one token and head at a time, as the
    equations stand: ``S = exp(g) S (I - beta k k^T) + beta v k^T``."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    B, length, H, d_k = q.shape
    out = np.zeros(v.shape)
    for b in range(B):
        for h in range(H):
            S = np.zeros((v.shape[-1], d_k))
            for t in range(length):
                kt, bt = k[b, t, h], beta[b, t, h]
                S = np.exp(g[b, t, h]) * S @ (
                    np.eye(d_k) - bt * np.outer(kt, kt)) \
                    + bt * np.outer(v[b, t, h], kt)
                out[b, t, h] = S @ q[b, t, h] / np.sqrt(d_k)
    return out


@pytest.mark.parametrize("length, chunk", [
    (50, 16),       # the chunk does not divide the row
    (64, 16),       # it does
    (8, 64),        # a row shorter than one chunk (the model's init)
    (130, gated_delta.CHUNK),   # the program's chunk, two whole and a part
])
def test_the_chunked_rule_is_the_token_by_token_recurrence(length, chunk):
    ops = _operands(length=length)
    got = chunked_rule(chunk)(*ops)
    if chunk == gated_delta.CHUNK:
        assert np.array_equal(np.asarray(gated_delta_rule(*ops)),
                              np.asarray(got))
    assert got.shape == ops[2].shape and got.dtype == ops[0].dtype
    assert np.allclose(got, loop_rule(*ops), atol=2e-5)
    assert np.allclose(reference_olmo_hybrid.delta_rule(*ops),
                       loop_rule(*ops), atol=2e-5)


@pytest.mark.parametrize("length, chunk, heads_a_pass", [
    (50, 16, 10), (64, 32, 10), (40, 16, 1)])
def test_every_gradient_of_the_custom_backward_is_autodiffs_of_the_recurrence(
        length, chunk, heads_a_pass, monkeypatch):
    """``heads_a_pass`` 1 with 3 heads: the backward takes the heads in
    three passes (``lax.map``), as it takes 30 in three at the published
    size."""
    monkeypatch.setattr(gated_delta, "HEADS_A_PASS", heads_a_pass)
    ops = _operands(seed=1, length=length)
    weigh = jnp.asarray(np.random.default_rng(2).normal(
        size=ops[2].shape), jnp.float32)
    loss = lambda rule: lambda *a: jnp.sum(rule(*a) * weigh)  # noqa: E731
    got = jax.grad(loss(chunked_rule(chunk)), argnums=range(5))(*ops)
    want = jax.grad(loss(reference_olmo_hybrid.delta_rule),
                    argnums=range(5))(*ops)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(
            jnp.abs(w).max()), name


def test_a_write_stronger_than_one_turns_the_old_value_over():
    """By hand, one head of ``d_k`` 2: a first token writes ``v1`` under key
    ``e1``; a second with the same key, ``beta`` 2 and no decay gives
    ``S = S (I - 2 e1 e1^T) + 2 v2 e1^T``: the old value's SIGN turns (the
    transition's eigenvalue along ``k`` is ``1 - beta = -1``); with ``beta``
    1 it is replaced."""
    e1 = jnp.asarray([[[[1.0, 0.0]]] * 3])                  # [1, 3, 1, 2]
    v = jnp.asarray([[[[3.0]], [[5.0]], [[0.0]]]])          # [1, 3, 1, 1]
    g = jnp.zeros((1, 3, 1))
    read = lambda beta: np.asarray(chunked_rule(2)(  # noqa: E731
        e1, e1, v, g, jnp.asarray(beta).reshape(1, 3, 1))
    )[0, :, 0, 0] * np.sqrt(2.0)
    assert np.allclose(read([1.0, 2.0, 0.0]), [3.0, -3.0 + 10.0, 7.0])
    assert np.allclose(read([1.0, 1.0, 0.0]), [3.0, 5.0, 5.0])
    # and a decay of a half a token fades what a write left
    half = jnp.full((1, 3, 1), np.log(0.5))
    faded = np.asarray(chunked_rule(2)(
        e1, e1, v, half, jnp.asarray([1.0, 0.0, 0.0]).reshape(1, 3, 1))
    )[0, :, 0, 0] * np.sqrt(2.0)
    assert np.allclose(faded, [3.0, 1.5, 0.75])


def test_no_token_reads_a_later_one_so_right_padding_is_harmless():
    ops = _operands(length=40)
    rule = chunked_rule(16)
    out = rule(*ops)
    garbage = tuple(x.at[:, 23:].set(7.0) for x in ops[:3]) + (
        ops[3].at[:, 23:].set(-3.0), ops[4].at[:, 23:].set(1.9))
    assert np.array_equal(np.asarray(rule(*garbage)[:, :23]),
                          np.asarray(out[:, :23]))
    grads = jax.grad(lambda *a: jnp.sum(rule(*a)[:, :23]),
                     argnums=range(5))(*ops)
    assert all(float(jnp.abs(g[:, 23:]).max()) == 0.0 for g in grads)


def test_the_backward_keeps_the_inputs_and_the_boundary_states_only():
    ops = _operands(length=64, dtype=jnp.bfloat16)
    out, vjp = jax.vjp(chunked_rule(16), *ops)
    shapes = sorted((tuple(x.shape), str(x.dtype))
                    for x in jax.tree_util.tree_leaves(vjp)
                    if hasattr(x, "shape"))
    B, length, H, d_k = ops[0].shape
    d_v = ops[2].shape[-1]
    assert shapes == sorted([
        ((B, length, H, d_k), "bfloat16"), ((B, length, H, d_k), "bfloat16"),
        ((B, length, H, d_v), "bfloat16"), ((B, length, H), "float32"),
        ((B, length, H), "float32"),
        ((length // 16, B, H, d_k, d_v), "float32")])   # a state a chunk
    assert out.dtype == jnp.bfloat16
    grads = vjp(jnp.ones_like(out))
    assert [g.dtype for g in grads] == [x.dtype for x in ops]


def test_the_solve_is_the_inverse_of_the_unit_lower_triangle():
    """``_solve(A, rhs) = (I + A)^-1 rhs`` for a strictly lower ``A``, a
    chunk and head at a time; what lies on or above ``A``'s diagonal is
    never read."""
    rng = np.random.default_rng(4)
    A = np.tril(rng.normal(size=(2, 3, 8, 8)), -1)
    rhs = rng.normal(size=(2, 3, 8, 5))
    want = np.linalg.solve(np.eye(8) + A, rhs)
    got = gated_delta._solve(jnp.asarray(A, jnp.float32),
                             jnp.asarray(rhs, jnp.float32))
    assert np.allclose(got, want, atol=1e-4)
    above = A + np.triu(rng.normal(size=A.shape))
    assert np.array_equal(np.asarray(got), np.asarray(gated_delta._solve(
        jnp.asarray(above, jnp.float32), jnp.asarray(rhs, jnp.float32))))


def test_mechanisms_the_operator_lacks_raise_by_name():
    """The operator scans whole rows from a zero state and takes nothing
    else; the trunk's ``unsupported()`` is where a mechanism is refused."""
    assert list(inspect.signature(gated_delta_rule).parameters) == [
        "q", "k", "v", "g", "beta"]
    with pytest.raises(NotImplementedError,
                       match=r"olmo_hybrid trunk \(full_attention / "
                             r"linear_attention \+ dense FFN\).*sequence "
                             r"packing \(the delta rule's state is not "
                             r"reset.*int8 serving"):
        mla_moe.unsupported(TINY, packing=True, quantize="int8")
    with pytest.raises(NotImplementedError, match="pipe mesh axis"):
        mla_moe.unsupported(TINY, mesh=build_mesh("data:2,pipe:2"))
    mla_moe.unsupported(TINY, mesh=build_mesh("data:2"))     # replicated: fine
    model = QAModel(TINY, attention_impl="xla")
    ids = jnp.ones((1, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)["params"]
    with pytest.raises(NotImplementedError, match="sequence packing"):
        model.apply({"params": params}, ids, segment_ids=ids,
                    position_ids=ids, segment_starts=jnp.zeros((1, 2),
                                                               jnp.int32))


def test_the_configuration_is_validated_when_it_is_built():
    with pytest.raises(ValueError, match="linear_attention for each of"):
        dataclasses.replace(TINY, layer_types=("linear", "full_attention"))
    with pytest.raises(ValueError, match="linear_num_heads"):
        dataclasses.replace(TINY, linear_key_head_dim=0)
    with pytest.raises(ValueError, match="qk_norm 'head' must be"):
        dataclasses.replace(TINY, qk_norm="head")
    assert TINY.scans and not TINY.routes
    assert not MODEL_PRESETS["lfm2-tiny"].scans
    assert MODEL_PRESETS["lfm2-tiny"].routes


# -- the layer's other parts ------------------------------------------------------------

def test_conv_and_silu_are_the_explicit_loop():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 5)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
    want = np.zeros((2, 9, 5))
    for t in range(9):
        for j in range(4):
            s = t - 3 + j                   # tap 3 reads the current position
            if s >= 0:
                want[:, t] += np.asarray(taps)[:, j] * np.asarray(x)[:, s]
    want = want / (1 + np.exp(-want))
    assert np.allclose(causal_conv_silu(x, taps), want, atol=1e-5)
    assert np.allclose(reference_olmo_hybrid.conv_silu(x, taps), want,
                       atol=1e-5)
    assert causal_conv_silu(x.astype(jnp.bfloat16), taps).dtype == jnp.float32
    got = jax.grad(lambda a, w: jnp.sum(causal_conv_silu(a, w) ** 2),
                   (0, 1))(x, taps)
    ref = jax.grad(lambda a, w: jnp.sum(
        reference_olmo_hybrid.conv_silu(a, w) ** 2), (0, 1))(x, taps)
    for g, w in zip(got, ref):
        assert np.allclose(g, w, atol=1e-4)


def test_the_gated_norm_by_hand():
    rng = np.random.default_rng(1)
    o = rng.normal(size=(2, 5, 3, 16))
    gate = rng.normal(size=(2, 5, 3, 16))
    scale = rng.normal(size=(16,))
    want = o / np.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-6) * scale \
        * gate / (1 + np.exp(-gate))
    got = mla_moe._gated_norm(jnp.asarray(o, jnp.float32),
                              jnp.asarray(gate, jnp.float32),
                              jnp.asarray(scale, jnp.float32), 1e-6,
                              jnp.float32)
    assert np.allclose(got, want, atol=1e-5)


def _layer(cfg, kind, **more):
    return mla_moe.DecoderLayer(cfg, True, jnp.float32, "xla", None, kind,
                                **more)


def _sub(module, params, x, *args):
    return module.apply({"params": params}, x, *args)


def _rms(x, scale, eps=1e-6):
    return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def test_the_norms_stand_on_the_outputs_of_the_operator_and_the_ffn():
    """``h = x + norm(Op(x))``, ``x' = h + norm(FFN(h))`` by hand from the
    layer's own sub-modules: the layer's input passes no norm."""
    layer = _layer(TINY, "full_attention")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 12, 64)),
                    jnp.float32)
    mask = jnp.ones((2, 12), jnp.int32)
    params = layer.init(jax.random.key(0), x, mask)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(1), a.shape),
        params)
    assert set(params) == {"attention", "mlp", "post_attention_layer_norm",
                           "post_feedforward_layer_norm"}
    got = layer.apply({"params": params}, x, mask)
    op = _sub(mla_moe.GroupedQueryAttention(TINY, jnp.float32, "xla"),
              params["attention"], x, mask)
    h = np.asarray(x) + _rms(
        np.asarray(op), np.asarray(params["post_attention_layer_norm"][
            "scale"]))
    ffn = _sub(mla_moe.GatedFFN(TINY, TINY.intermediate_size, jnp.float32),
               params["mlp"], jnp.asarray(h, jnp.float32))
    want = h + _rms(np.asarray(ffn), np.asarray(
        params["post_feedforward_layer_norm"]["scale"]))
    assert np.allclose(got, want, atol=2e-5)
    # and the pre-norm trunk's layer is untouched by the switch
    pre = _layer(dataclasses.replace(TINY, norm_after=False),
                 "full_attention")
    assert set(pre.init(jax.random.key(0), x, mask)["params"]) == {
        "attention", "mlp", "input_layer_norm", "post_attention_layer_norm"}


def test_q_and_k_are_normed_over_the_whole_projection_and_nothing_rotates():
    """By hand: ``q = RMSNorm_64(x W_q)`` BEFORE the split into heads, one
    scale of the projection's width; scores are position-blind (no rotation):
    a permutation of earlier tokens permutes the weights and leaves the last
    token's output as it was."""
    attn = mla_moe.GroupedQueryAttention(TINY, jnp.float32, "xla")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 6, 64)), jnp.float32)
    mask = jnp.ones((1, 6), jnp.int32)
    params = attn.init(jax.random.key(0), x, mask)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(3), a.shape),
        params)
    assert params["q_layer_norm"]["scale"].shape == (64,)
    assert params["k_layer_norm"]["scale"].shape == (64,)
    p = jax.tree_util.tree_map(np.asarray, params)
    xs = np.asarray(x)[0]
    q = _rms(xs @ p["q"]["kernel"], p["q_layer_norm"]["scale"]).reshape(
        6, 4, 16)
    k = _rms(xs @ p["k"]["kernel"], p["k_layer_norm"]["scale"]).reshape(
        6, 4, 16)
    v = (xs @ p["v"]["kernel"]).reshape(6, 4, 16)
    s = np.einsum("qhd,khd->hqk", q, k) / 4.0
    s = np.where(np.tril(np.ones((6, 6), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", w, v).reshape(6, 64) @ p["output"][
        "kernel"]
    got = attn.apply({"params": params}, x, mask)
    assert np.allclose(got[0], want, atol=2e-5)
    shuffled = x[:, jnp.asarray([3, 0, 4, 1, 2, 5])]
    again = attn.apply({"params": params}, shuffled, mask)
    assert np.allclose(again[0, -1], got[0, -1], atol=2e-5)
    # a rotating trunk is not position-blind
    rotating = mla_moe.GroupedQueryAttention(
        dataclasses.replace(TINY, rope_theta=10000.0), jnp.float32, "xla")
    assert not np.allclose(
        rotating.apply({"params": params}, shuffled, mask)[0, -1],
        rotating.apply({"params": params}, x, mask)[0, -1], atol=1e-3)


# -- the system against the reference ---------------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    """Seeded weights widened so that every term shows: a tap, a decay and a
    write strength that vary, norm scales off one, head biases off zero."""
    model = QAModel(TINY, attention_impl="xla")
    inputs, labels = checks.seeded_rows(7, TINY.vocab_size, L,
                                        [L, 40, 29, 11])
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]

    def widen(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        key = jax.random.key(zlib.crc32(name.encode()))
        if name.endswith("taps"):
            return a * 25
        if name.endswith(("/attention/q/kernel", "/attention/k/kernel")):
            # heads of unlike size: a norm a head is not the whole width's
            return a * jnp.repeat(jnp.asarray([4.0, 1.0, 0.25, 1.0]), 16)
        if name.endswith(("a/kernel", "b/kernel")):
            return a * 12
        if "layer_norm" in name:
            return a + 0.2 * jax.random.normal(key, a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(widen, params)
    for key, name in zip(jax.random.split(jax.random.key(2), 4), (
            "position_outputs", "classifier", "reg_start", "reg_end")):
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
    want, own = reference_olmo_hybrid.forward(params, ref_cfg(), **inputs,
                                              q_block=16)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    assert max(errors.values()) < 5e-5, errors      # float32 against float32
    assert len(own["scan"]) == 3
    loss_fn = recipe_loss()
    device_labels = {k: jnp.asarray(v) for k, v in labels.items()}

    def system_loss(p):
        return loss_fn(system_outputs(model, p, inputs), device_labels)[0]

    def reference_loss(p):
        preds, _ = reference_olmo_hybrid.forward(p, ref_cfg(), **inputs,
                                                 q_block=16)
        return reference_olmo_hybrid.loss(preds, labels, smooth_alpha=0.01)

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
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, name
        seen.add(name.split("/")[2] if name.startswith("transformer/layer")
                 else name.split("/")[0])
    assert {"linear_attention", "attention", "mlp",
            "post_feedforward_layer_norm"} <= seen


def _plain_beta(params, cfg, monkeypatch):
    return params, dict(cfg, linear_allow_neg_eigval=False)


def _no_decay(params, cfg, monkeypatch):
    monkeypatch.setattr(reference_olmo_hybrid, "decay_of",
                        lambda g: jnp.ones_like(g))
    return params, cfg


def _no_l2norm(params, cfg, monkeypatch):
    monkeypatch.setattr(reference_olmo_hybrid, "l2norm", lambda x: x)
    return params, cfg


def _no_gate(params, cfg, monkeypatch):
    original = reference_olmo_hybrid._rms

    def ungated(scale, x, eps):     # the gated norm's RMSNorm, left out
        gated_norm = np.shape(scale) == (cfg["linear_value_head_dim"],)
        return x if gated_norm else original(scale, x, eps)
    monkeypatch.setattr(reference_olmo_hybrid, "_rms", ungated)
    return params, cfg


def _reversed_taps(params, cfg, monkeypatch):
    original = reference_olmo_hybrid.conv_silu
    monkeypatch.setattr(
        reference_olmo_hybrid, "conv_silu",
        lambda z, taps: original(z, jnp.asarray(taps)[:, ::-1]))
    return params, cfg


def _pre_norm(params, cfg, monkeypatch):
    """The norms on the inputs, as the trunk's other configurations have
    them."""
    def pre(p, c, ids, mask, q_block):
        ref = reference_olmo_hybrid
        t = p["transformer"]
        eps = c["rms_norm_eps"]
        x = ref._f32(t["word_embeddings"]["embedding"])[ids]
        for i, kind in enumerate(c["layer_types"]):
            layer = t[f"layer_{i}"]
            u = ref._rms(layer["post_attention_layer_norm"]["scale"], x, eps)
            y = ref._linear_attention(layer["linear_attention"], c, u)[0] \
                if kind == "linear_attention" else ref._attention(
                    layer["attention"], c, u, mask, q_block)
            h = x + y
            x = h + ref._swiglu(layer["mlp"], ref._rms(
                layer["post_feedforward_layer_norm"]["scale"], h, eps))
        x = ref._rms(t["final_layer_norm"]["scale"], x, eps)
        span = x @ ref._f32(p["position_outputs"]["kernel"]) + ref._f32(
            p["position_outputs"]["bias"])
        pooled = x[jnp.arange(x.shape[0]), jnp.maximum(mask.sum(-1) - 1, 0)]
        head = lambda n: pooled @ ref._f32(p[n]["kernel"]) + ref._f32(  # noqa: E731
            p[n]["bias"])
        pad = (1 - mask).astype(jnp.float32) * ref.MASK_NEG
        return {"start_class": span[..., 0] + pad,
                "end_class": span[..., 1] + pad, "cls": head("classifier"),
                "start_reg": jax.nn.sigmoid(head("reg_start"))[..., 0],
                "end_reg": jax.nn.sigmoid(head("reg_end"))[..., 0]}, {
                    "scan": []}
    monkeypatch.setattr(reference_olmo_hybrid, "_forward", pre)
    return params, cfg


def _head_norm(params, cfg, monkeypatch):
    """The q/k norm a head (the trunk's other reach) in place of the whole
    projection's: each head's 16 of the scale's 64 normalise that head."""
    original = reference_olmo_hybrid._rms

    def a_head(scale, x, eps):
        if x.shape[-1] != cfg["hidden_size"] or np.shape(scale) != (
                cfg["hidden_size"],) or not getattr(a_head, "on", False):
            return original(scale, x, eps)
        H = cfg["num_attention_heads"]
        heads = x.reshape(x.shape[:-1] + (H, -1))
        return (heads * jax.lax.rsqrt(jnp.mean(
            heads * heads, -1, keepdims=True) + eps)).reshape(
            x.shape) * jnp.asarray(scale)

    original_attention = reference_olmo_hybrid._attention

    def attention(*args):
        a_head.on = True
        try:
            return original_attention(*args)
        finally:
            a_head.on = False
    monkeypatch.setattr(reference_olmo_hybrid, "_rms", a_head)
    monkeypatch.setattr(reference_olmo_hybrid, "_attention", attention)
    return params, cfg


@pytest.mark.parametrize("drop", [
    _plain_beta, _no_decay, _no_l2norm, _no_gate, _reversed_taps, _pre_norm,
    _head_norm])
def test_a_dropped_term_lands_outside_the_benchmarks_tolerance(
        seeded, drop, monkeypatch):
    """Each term of the mathematics, changed in the reference alone: the
    system's logits then miss it by more than the benchmark allows."""
    from perfbench.harness import checks_olmo_hybrid

    model, params, inputs, _ = seeded
    got = system_outputs(model, params, inputs)
    changed, cfg = drop(params, ref_cfg(), monkeypatch)
    want, _ = reference_olmo_hybrid.forward(changed, cfg, **inputs,
                                            q_block=16)
    errors = checks.absolute_errors(got, want, inputs["attention_mask"])
    tolerances = checks_olmo_hybrid.logit_tolerances(params, TINY.num_layers)
    assert not checks.within(errors, tolerances), (errors, tolerances)


# -- the counters of a trunk with no expert layer, and the Trainer ----------------------

def test_step_stats_follow_the_configuration():
    """A trunk with no expert layer has no routing counter to divide by the
    number of expert layers (the parent read ``sum / float(0)`` here)."""
    dense_attention = dataclasses.replace(
        MODEL_PRESETS["lfm2-tiny"], first_k_dense_replace=4,
        layer_types=("full_attention",) * 4)
    assert mla_moe.step_stat_keys(dense_attention) == ()
    assert mla_moe.step_stat_keys(TINY) == mla_moe.SCAN_STAT_KEYS
    assert mla_moe.step_stat_keys(MODEL_PRESETS["lfm2-tiny"]) \
        == mla_moe.STEP_STAT_KEYS
    both = dataclasses.replace(TINY, first_k_dense_replace=2, experts_held=8,
                               n_routed_experts=8, num_experts_per_tok=2,
                               moe_intermediate_size=32)
    assert mla_moe.step_stat_keys(both) == mla_moe.STEP_STAT_KEYS \
        + mla_moe.SCAN_STAT_KEYS
    ids = jnp.asarray(np.random.default_rng(0).integers(5, 500, (2, 24)),
                      jnp.int32)
    mask = jnp.ones_like(ids).at[1, 17:].set(0)
    for cfg in (dense_attention, TINY, both):
        model = QAModel(cfg, attention_impl="xla")
        params = model.init(jax.random.key(0), ids)["params"]
        preds, stats = model.apply_with_stats({"params": params}, ids, mask)
        assert set(stats) == set(model.step_stat_keys)
        assert all(np.isfinite(float(v)) for v in stats.values())
        assert set(preds) == {"start_class", "end_class", "start_reg",
                              "end_reg", "cls"}
    # the scan's two, by hand from what the layers sowed (real tokens only)
    model = QAModel(TINY, attention_impl="xla")
    params = model.init(jax.random.key(0), ids)["params"]
    _, sown = model.apply({"params": params}, ids, mask,
                          mutable=[mla_moe.ROUTING])
    layers = [sown[mla_moe.ROUTING]["transformer"][f"layer_{i}"][
        "linear_attention"] for i in range(3)]
    real = np.asarray(mask, bool)
    decay = np.mean([np.exp(np.asarray(s["scan_input"][0][3]))[real].mean()
                     for s in layers])
    beta = np.mean([np.asarray(s["scan_input"][0][4])[real].mean()
                    for s in layers])
    stats = mla_moe.step_stats(sown[mla_moe.ROUTING])
    assert float(stats["linear_decay_mean"]) == pytest.approx(decay, rel=1e-5)
    assert float(stats["linear_beta_mean"]) == pytest.approx(beta, rel=1e-5)
    assert 0.0 < decay < 1.0 and 0.5 < beta < 1.5


def test_the_decay_parameters_take_no_weight_decay():
    from ml_recipe_tpu.train.optim import no_decay_mask

    params = QAModel(TINY, attention_impl="xla").init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    mask = no_decay_mask(params)["transformer"]["layer_0"]["linear_attention"]
    assert not mask["A_log"] and not mask["dt_bias"]
    assert not mask["o_layer_norm"]
    assert mask["q_taps"] and mask["q"]["kernel"] and mask["a"]["kernel"]
    p = params["transformer"]["layer_0"]["linear_attention"]
    assert float(jnp.exp(p["A_log"]).max()) <= 16.0
    dt = jax.nn.softplus(p["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01


def test_a_step_with_remat_on_is_the_step_with_it_off(tmp_path):
    """``QAModel`` -> ``Trainer`` -> ``build_step`` at ``batch_split`` 1, the
    cell's shape: the layers sow through ``nn.remat`` as they do without it,
    the counters reach the meters, every kind of leaf moves, and both steps
    give one loss and the same parameters."""
    after, last = {}, {}
    for remat in (False, True):
        seen = []
        trainer = make_trainer(
            tmp_path / f"remat_{remat}", batch_split=1, preset=TINY,
            on_train_metrics=lambda meters, step: seen.append(
                {k: float(m()) for k, m in meters.items() if k != "lr"}))
        trainer.model = dataclasses.replace(trainer.model, remat=remat)
        before = jax.device_get(trainer.params["transformer"])
        trainer.train()
        after[remat] = jax.device_get(trainer.params)
        assert trainer.global_step == 2 and np.isfinite(seen[-1]["loss"])
        assert trainer.batch_split == 1
        moved = lambda *path: not np.array_equal(  # noqa: E731
            *(np.asarray(_at(t, path)) for t in (
                before, after[remat]["transformer"])))
        for leaf in ("q_taps", "A_log", "dt_bias", "o_layer_norm"):
            assert moved("layer_0", "linear_attention", leaf), leaf
        assert moved("layer_1", "linear_attention", "b", "kernel")
        assert moved("layer_3", "attention", "q_layer_norm", "scale")
        assert moved("layer_2", "post_feedforward_layer_norm", "scale")
        assert 0.0 < seen[-1]["linear_decay_mean"] < 1.0
        assert 0.9 < seen[-1]["linear_beta_mean"] < 1.1
        assert "moe_held_assignments" not in seen[-1]
        last[remat] = seen[-1]
    for key in ("loss", "linear_decay_mean", "linear_beta_mean"):
        assert last[True][key] == pytest.approx(last[False][key], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(after[True]),
                    jax.tree_util.tree_leaves(after[False])):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-6)


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# -- the readings behind the comparison's limits, at the tiny size -----------------------

@pytest.fixture(scope="module")
def verdicts():
    """``scripts/olmo_hybrid_tolerance_readings.py --rehearse``: the script's
    own path (the cell's tiny configuration, bf16) through ``compare``, once
    for the system and once for each lowered control."""
    import contextlib
    import importlib.util
    import io
    import json

    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_tolerance_readings",
        REPO / "scripts" / "olmo_hybrid_tolerance_readings.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert module.main(["--rehearse", "--seeds", "3300000913"]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["seed"] == 3300000913
    return line["verdicts"]


@pytest.mark.parametrize("control, caught_by", [
    ("system", None),
    ("bf16_state", "scan_on_one_input"),
    ("bf16_solve", "scan_on_one_input"),
    ("beta_without_2", "scan_inputs_along_the_trajectory"),
    ("no_decay", "scan_inputs_along_the_trajectory"),
    ("no_l2norm", "scan_inputs_along_the_trajectory"),
    ("float8_matmuls", "logits"),
])
def test_the_comparison_passes_the_system_and_names_what_catches_a_control(
        verdicts, control, caught_by):
    verdict = verdicts[control]
    assert set(verdict) >= {"ok", "failed_parts", "scan", "logit_abs_err",
                            "logit_tol", "loss", "reference_loss"}
    layers = verdict["scan"]["layers"]
    assert len(layers) == len(verdict["scan"]["input_drift"]) == 3
    if caught_by is None:
        assert verdict["ok"] and verdict["failed_parts"] == []
        assert all(layer["beyond_one_rounding_share"] == 0.0
                   for layer in layers)
        assert all(v < 0.02 for d in verdict["scan"]["input_drift"]
                   for v in d.values())
    else:
        assert not verdict["ok"] and caught_by in verdict["failed_parts"]
    if control in ("bf16_state", "bf16_solve"):
        # no logit can tell at this size: the operator's own part does
        assert "logits" not in verdict["failed_parts"]
        assert all(layer["beyond_one_rounding_share"] > 0.01
                   for layer in layers)


def test_the_backward_script_rehearses_without_a_time(capsys):
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "gated_delta_bwd_on_chip",
        REPO / "scripts" / "gated_delta_bwd_on_chip.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.main(["--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "forward_ms" not in line and "forward_backward_ms" not in line
    assert line["form_chosen"] == "xla"         # what this backend runs
    names = {"q", "k", "v", "g", "beta"}
    for form in ("xla", "kernel"):      # each against the recurrence
        found = line["gradients_max_abs_diff_over_largest"][form]
        assert set(found) == names and max(found.values()) < 1e-2
        assert line["forward_beyond_one_bf16_rounding_share"][form] == 0.0
    found = line["kernel_gradients_against_the_xla_forms_at_forward_len"]
    assert set(found) == names and max(found.values()) < 1e-2

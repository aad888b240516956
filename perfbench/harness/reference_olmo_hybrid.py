"""The plain reference of the ``olmo_hybrid`` trunk (Olmo-Hybrid-7B,
https://huggingface.co/allenai/Olmo-Hybrid-7B) with the recipe's QA heads and
loss, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernels, no bf16, no chunks,
nothing imported from ``ml_recipe_tpu``. Written from the equations below,
not from the program: the recurrence runs TOKEN BY TOKEN (``lax.scan`` over
positions, the update as it is written, no chunk and no transform), the
convolution is an explicit sum of shifted copies, and attention is the full
masked softmax a block of queries at a time so that L = 8,192 fits. A step's
decay ``exp(g_t)`` is evaluated by hand (``decay_of``), not by the chip's
``exp``: see there.

Equations (``cfg`` is the configuration file, parameters the nested dict the
system's checkpoint holds; every projection bias-free; ``RMS`` has eps
``rms_norm_eps`` and a learned scale):

- ``x = E[ids]``; layer ``l``: ``h = x + RMS(Op_l(x))``,
  ``x' = h + RMS(FFN(h))`` (the norms stand on the OUTPUT of the operator and
  of the FFN; a layer's input passes no norm); ``RMS`` once more after the
  last layer. ``FFN(h) = W_down(silu(h W_gate) * (h W_up))`` in every layer.
- ``linear_attention`` (``H = linear_num_value_heads = linear_num_key_heads``
  heads, ``d_k = linear_key_head_dim``, ``d_v = linear_value_head_dim``,
  ``K = linear_conv_kernel_dim``), ``u`` the layer's input:

      q, k = l2norm(silu(conv(u W_q))), l2norm(silu(conv(u W_k)))   a head, eps 1e-6
      v    = silu(conv(u W_v))
      conv(z)[t, d] = sum_j w[d, j] z[t - (K-1) + j, d]              z = 0 before position 0
      beta = 2 sigmoid(u W_b)               (2: linear_allow_neg_eigval; else 1)
      g    = -exp(A_log) softplus(u W_a + dt_bias)
      S_t  = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_0 = 0, [d_v, d_k]
      o_t  = S_t q_t / sqrt(d_k)
      y    = (RMS_{d_v}(o) * silu(u W_g)) W_o          one scale of d_v, shared by the heads

- ``full_attention``: ``q = RMS(u W_q)``, ``k = RMS(u W_k)`` over the WHOLE
  projection's width, then ``num_attention_heads`` heads of ``hidden_size /
  num_attention_heads`` (as many key/value heads); no rotation
  (``rope_theta`` null); causal softmax at ``d ** -0.5``; ``W_o``.

Departures from the published model, the system's and noted: no LM head
(untied there; the recipe has no token-level loss); the class and regressor
heads read each row's last attended token; span logits at padded positions
are pushed to -1e9. The norm placement, the whole-width q/k norm and "null
means no rotation" are inferences from the Olmo 2 / Olmo 3 family (the
configuration file lists them under ``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import loss  # noqa: F401 - the recipe's loss, model-independent

MASK_NEG = -1e9
L2_EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _matmul(x, w):
    """Every matrix product of the projections and the FFN (one place to
    lower the precision of, for the readings behind the comparison's
    limits)."""
    return x @ w


def _mm(x, p):
    return _matmul(x, _f32(p["kernel"]))


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


def conv_silu(z, taps):
    """``silu(conv(z))`` of ``z`` [B, L, D] by ``taps`` [D, K]."""
    B, L, D = z.shape
    K = taps.shape[-1]
    c = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j        # tap j reads position t - back
        behind = jnp.concatenate(
            [jnp.zeros((B, back, D), z.dtype), z[:, :L - back]], axis=1)
        c = c + _f32(taps)[:, j] * behind
    return jax.nn.silu(c)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


LN2_HI, LN2_LO = 0.693145751953125, 1.42860682030941723212e-6


def decay_of(g):
    """``exp(g)`` for ``g <= 0`` to float32's last bits, by hand: ``2^n
    exp(r)`` with ``n = round(g / ln 2)``, ``r = g - n ln 2`` (``ln 2`` in two
    parts) and the series to ``r^9 / 9!`` (``|r| <= 0.35``: the remainder is
    under 1e-11). The recurrence multiplies a step's decay into the state
    8,192 times over, and the v5e's own ``exp`` reads up to 5e-6 off with one
    sign for like arguments: token by token that compounds to percents of the
    oldest contributions (0.04-0.2% of a layer's outputs then lay 10-70 bf16
    roundings from the chunked form, which takes one ``exp`` a chunk; none
    with the decay off; PERF.md section 6, PR 33)."""
    g = _f32(g)
    n = jnp.round(g / (LN2_HI + LN2_LO))
    r = (g - n * LN2_HI) - n * LN2_LO
    series = jnp.ones_like(r)
    for i in range(9, 0, -1):
        series = 1.0 + series * r / i
    return jnp.ldexp(series, n.astype(jnp.int32))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token. ``q``, ``k`` [B, L, H, d_k], ``v``
    [B, L, H, d_v], ``g`` and ``beta`` [B, L, H]; returns ``o``
    [B, L, H, d_v], float32."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = (jnp.moveaxis(_f32(x), 1, 0)
                            for x in (q, k, v, g, beta))
        d_k = q.shape[-1]
        eye = jnp.eye(d_k, dtype=jnp.float32)

        def token(S, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            b_t = b_t[..., None, None]
            forget = eye - b_t * k_t[..., :, None] * k_t[..., None, :]
            S = decay_of(g_t)[..., None, None] * (S @ forget) \
                + b_t * v_t[..., :, None] * k_t[..., None, :]
            return S, (S @ q_t[..., None])[..., 0] * d_k ** -0.5

        S0 = jnp.zeros(q.shape[1:3] + (v.shape[-1], d_k), jnp.float32)
        return jnp.moveaxis(
            jax.lax.scan(token, S0, (q, k, v, g, beta))[1], 0, 1)


def scan_inputs(p, cfg, u):
    """``(q, k, v, g, beta)`` of a ``linear_attention`` layer ``p`` on its
    input ``u``: what its recurrence reads."""
    B, L, _ = u.shape
    H = cfg["linear_num_value_heads"]
    assert cfg["linear_num_key_heads"] == H, "as many key as value heads"
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    q = l2norm(conv_silu(_mm(u, p["q"]), p["q_taps"]).reshape(B, L, H, d_k))
    k = l2norm(conv_silu(_mm(u, p["k"]), p["k_taps"]).reshape(B, L, H, d_k))
    v = conv_silu(_mm(u, p["v"]), p["v_taps"]).reshape(B, L, H, d_v)
    beta = jax.nn.sigmoid(_mm(u, p["b"])) * (
        2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        _mm(u, p["a"]) + _f32(p["dt_bias"]))
    return q, k, v, g, beta


def _linear_attention(p, cfg, u):
    """``(y, (q, k, v, g, beta), o)``."""
    B, L, _ = u.shape
    read = scan_inputs(p, cfg, u)
    o = delta_rule(*read)
    gate = jax.nn.silu(_mm(u, p["g"])).reshape(o.shape)
    gated = _rms(p["o_layer_norm"], o, cfg["rms_norm_eps"]) * gate
    return _mm(gated.reshape(B, L, -1), p["output"]), read, o


def _attention(p, cfg, u, mask, q_block):
    B, L, C = u.shape
    H = cfg["num_attention_heads"]
    assert cfg["num_key_value_heads"] == H, "as many key/value as query heads"
    assert cfg["rope_parameters"]["rope_theta"] is None, "nothing is rotated"
    d = C // H
    eps = cfg["rms_norm_eps"]
    q = _rms(p["q_layer_norm"]["scale"], _mm(u, p["q"]), eps).reshape(
        B, L, H, d)
    k = _rms(p["k_layer_norm"]["scale"], _mm(u, p["k"]), eps).reshape(
        B, L, H, d)
    v = _mm(u, p["v"]).reshape(B, L, H, d)
    block = min(q_block, L)
    assert L % block == 0, (L, block)

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        allowed = (jnp.arange(L)[None, :] <= rows[:, None])[None, None] \
            & (mask[:, None, None, :] > 0)
        probs = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, L, block))   # [n, B, blk, H, d]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, L, H * d)
    return _mm(ctx, p["output"])


def forward(params, cfg: dict, input_ids, attention_mask, token_type_ids=None,
            *, q_block: int = 128):
    """``(predictions, {'scan': [...]})``: the QA heads' outputs in float32
    and, per ``linear_attention`` layer, what its recurrence read (``q, k, v,
    g, beta``) and wrote (``o``)."""
    del token_type_ids      # the model has no such table
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(input_ids),
                        jnp.asarray(attention_mask), q_block)


def _forward(params, cfg, ids, mask, q_block):
    t = params["transformer"]
    eps = cfg["rms_norm_eps"]
    x = _f32(t["word_embeddings"]["embedding"])[ids]
    scans = []
    for i, kind in enumerate(cfg["layer_types"]):
        layer = t[f"layer_{i}"]
        if kind == "linear_attention":
            y, read, wrote = _linear_attention(
                layer["linear_attention"], cfg, x)
            scans.append((read, wrote))
        else:
            assert kind == "full_attention", kind
            y = _attention(layer["attention"], cfg, x, mask, q_block)
        h = x + _rms(layer["post_attention_layer_norm"]["scale"], y, eps)
        x = h + _rms(layer["post_feedforward_layer_norm"]["scale"],
                     _swiglu(layer["mlp"], h), eps)
    x = _rms(t["final_layer_norm"]["scale"], x, eps)
    last = jnp.maximum(mask.sum(-1) - 1, 0)
    pooled = x[jnp.arange(x.shape[0]), last]
    head = lambda name, y: _matmul(y, _f32(params[name]["kernel"])) + _f32(  # noqa: E731
        params[name]["bias"])
    span = head("position_outputs", x)
    pad = (1 - mask).astype(jnp.float32) * MASK_NEG
    preds = {
        "start_class": span[..., 0] + pad,
        "end_class": span[..., 1] + pad,
        "cls": head("classifier", pooled),
        "start_reg": jax.nn.sigmoid(head("reg_start", pooled))[..., 0],
        "end_reg": jax.nn.sigmoid(head("reg_end", pooled))[..., 0],
    }
    return preds, {"scan": scans}

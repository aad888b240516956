"""The plain reference of the ``lfm2_moe`` trunk (LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B) with the recipe's QA heads and
loss, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernels, no bf16, nothing
imported from ``ml_recipe_tpu``. Written from the equations below, not from
the program: the convolution is an explicit sum of shifted copies, grouped
key/value heads are repeated with ``jnp.repeat``, the experts are a dense
mixture (every held expert over every token, weighed by a one-hot sum), and
attention is the full masked softmax a block of queries at a time so that
L = 8,192 fits.

Equations (``cfg`` is the configuration file, parameters the nested dict the
system's checkpoint holds; every projection bias-free):

- ``x = E[ids]``; layer ``l``: ``r = x + Op_l(RMS(x))``,
  ``x' = r + FFN_l(RMS(r))``; ``RMS`` (eps ``norm_eps``, a learned scale)
  once more after the last layer (the published ``embedding_norm``). ``Op_l``
  is the short convolution where ``layer_types[l] == "conv"`` and attention
  where it is ``"full_attention"``.
- Short convolution, ``K = conv_L_cache`` taps: ``[Bg | Cg | x] = u W_in``
  (thirds in that order); ``z = Bg * x``;
  ``c[t, d] = sum_j w[d, j] * z[t - (K-1) + j, d]``, ``z`` zero before
  position 0; ``y = (Cg * c) W_out``.
- Attention: ``q = u W_q`` (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``), ``k = u W_k``, ``v = u W_v``
  (``num_key_value_heads``); ``q`` and ``k`` through an RMS norm over the
  head's width (one scale each, shared by the heads); RoPE in the half-split
  convention (``x * cos + rotate_half(x) * sin``: pairs ``(x[i], x[i + d/2])``,
  positions 0..L-1, theta ``rope_theta``, no scaling); causal softmax at
  ``d ** -0.5``, query head ``i`` reads key/value head ``i // group``;
  ``W_o``.
- FFN of the first ``num_dense_layers`` layers:
  ``W_down(silu(x W_gate) * (x W_up))``, width ``intermediate_size``.
- FFN of the others: ``s = sigmoid(x W_r)`` over all experts; chosen = top-k
  of ``s + expert_bias``; ``w_i = routed_scaling_factor * s_i / (sum_chosen s
  + 1e-6)``; ``y = sum_{i chosen and held} w_i Expert_i(x)``, no shared
  expert. Only the experts ``experts_held.first .. first + count - 1`` are
  here: the rest of the sum is another chip's, left out here as in the
  system.

Departures from the published model, the system's and noted: no LM head (tied
to the embedding there; the recipe has no token-level loss); the class and
regressor heads read each row's last attended token; span logits at padded
positions are pushed to -1e9; ``expert_bias`` is a constant.

``forward`` also returns, per expert layer, the experts chosen and the margin
between the k-th and (k+1)-th biased score: top-4 of 32 flips under bf16
rounding of the hidden state wherever that margin is small, so a comparison
may hand the system's choice back in (``routing``) and judge the routing
apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import loss  # noqa: F401 - the recipe's loss, model-independent

MASK_NEG = -1e9
NORM_TOPK_EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(p["scale"])


def _matmul(x, w):
    """Every matrix product of this file (one place to lower the precision
    of, for the readings behind the comparison's limits)."""
    return x @ w


def _gating(x):
    """Every elementwise result of the short convolution passes here (the
    one place to round them, for the same readings)."""
    return x


def _mm(x, p):
    return _matmul(x, _f32(p["kernel"]))


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


def gated_conv(bcx, taps):
    """``Cg * conv(Bg * x)`` of ``bcx`` [B, L, 3D] = ``[Bg | Cg | x]`` and
    ``taps`` [D, K]: the operator between its two projections."""
    B, L, width = bcx.shape
    D, K = width // 3, taps.shape[-1]
    gate_b, gate_c, x = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    z = _gating(gate_b * x)
    c = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j        # tap j reads position t - back
        behind = jnp.concatenate(
            [jnp.zeros((B, back, D), z.dtype), z[:, :L - back]], axis=1)
        c = _gating(c + _gating(_f32(taps)[:, j] * behind))
    return _gating(gate_c * c)


def rope_half_split(x, theta):
    """``x * cos + rotate_half(x) * sin`` over ``x`` [B, L, H, d]: the pair
    ``(x[i], x[i + d/2])`` turned by ``position * theta ** (-2i / d)``."""
    L, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def _attention(p, cfg, u, mask, q_block):
    B, L, C = u.shape
    H, H_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = C // H
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q = _mm(u, p["q"]).reshape(B, L, H, d)
    k = _mm(u, p["k"]).reshape(B, L, H_kv, d)
    v = _mm(u, p["v"]).reshape(B, L, H_kv, d)
    q = rope_half_split(_rms(p["q_layer_norm"], q, eps), theta)
    k = rope_half_split(_rms(p["k_layer_norm"], k, eps), theta)
    k, v = (jnp.repeat(x, H // H_kv, axis=2) for x in (k, v))
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


def _router_scores(x, kernel):
    return jax.nn.sigmoid(_matmul(x, kernel))


def route(p, cfg, x):
    """``(chosen [..., K], margin [...], scores [..., E])`` of router ``p``
    (``kernel``, ``bias``) on states ``x``: top-k of the biased sigmoid scores
    and the k-th less the (k+1)-th biased score."""
    with jax.default_matmul_precision("highest"):
        K = cfg["num_experts_per_tok"]
        scores = _router_scores(_f32(x), _f32(p["kernel"]))
        top, chosen = jax.lax.top_k(scores + _f32(p["bias"]), K + 1)
        return chosen[..., :K], top[..., K - 1] - top[..., K], scores


def _expert_layer(p, cfg, x, chosen=None):
    """``(y, chosen, margin)``; ``chosen`` [B, L, K] overrides the top-k (the
    weights still come from this function's own scores)."""
    first, count = cfg["experts_held"]["first"], cfg["experts_held"]["count"]
    own, margin, scores = route(p["router"], cfg, x)
    if chosen is None:
        chosen = own
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + NORM_TOPK_EPS)
    weights = picked * cfg["routed_scaling_factor"]
    # the dense mixture: a token's weight on expert e is the one-hot sum of
    # its chosen slots, zero for most tokens
    ids = first + jnp.arange(count)
    mixture = jnp.sum(
        (chosen[..., None] == ids) * weights[..., None], axis=-2)  # [B, L, E]
    experts = p["experts"]
    y = jnp.zeros_like(x)
    for e in range(count):
        hidden = jax.nn.silu(_matmul(x, _f32(experts["gate"][e]))) * _matmul(
            x, _f32(experts["up"][e]))
        y = y + mixture[..., e, None] * _matmul(
            hidden, _f32(experts["down"][e]))
    return y, own, margin


def forward(params, cfg: dict, input_ids, attention_mask, token_type_ids=None,
            *, routing=None, q_block: int = 128):
    """``(predictions, {'chosen': [...], 'margin': [...], 'router_input':
    [...], 'conv': [...]})``: the QA heads' outputs in float32 and, per expert
    layer, the reference's own top-k [B, L, K], its margin [B, L] and the
    state its router read [B, L, hidden]; per conv layer what the operator
    read and wrote between its projections. ``routing``: one [B, L, K] choice
    per expert layer to use instead of the top-k."""
    del token_type_ids      # the model has no such table
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(input_ids),
                        jnp.asarray(attention_mask), routing, q_block)


def _forward(params, cfg, ids, mask, routing, q_block):
    t = params["transformer"]
    eps = cfg["norm_eps"]
    x = _f32(t["word_embeddings"]["embedding"])[ids]
    chosen, margins, states, convs = [], [], [], []
    for i, kind in enumerate(cfg["layer_types"]):
        layer = t[f"layer_{i}"]
        u = _rms(layer["input_layer_norm"], x, eps)
        if kind == "conv":
            conv = layer["conv"]
            read = _mm(u, conv["in_proj"])
            wrote = gated_conv(read, conv["taps"])
            convs.append((read, wrote))
            r = x + _mm(wrote, conv["out_proj"])
        else:
            assert kind == "full_attention", kind
            r = x + _attention(layer["attention"], cfg, u, mask, q_block)
        u = _rms(layer["post_attention_layer_norm"], r, eps)
        if i < cfg["num_dense_layers"]:
            y = _swiglu(layer["mlp"], u)
        else:
            given = None if routing is None else jnp.asarray(
                routing[len(chosen)])
            y, own, margin = _expert_layer(layer["mlp"], cfg, u, given)
            chosen.append(own)
            margins.append(margin)
            states.append(u)
        x = r + y
    x = _rms(t["final_layer_norm"], x, eps)
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
    return preds, {"chosen": chosen, "margin": margins,
                   "router_input": states, "conv": convs}

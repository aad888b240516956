"""The plain reference of the ``joyai_llm_flash`` trunk (JoyAI-LLM-Flash,
https://huggingface.co/jdopensource/JoyAI-LLM-Flash: the DeepSeek-V3 block)
with the recipe's QA heads and loss, in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No kernels, no
bf16, nothing imported from ``ml_recipe_tpu``. The experts are a Python loop
over the held range, every expert over every token; attention is the full
masked softmax, computed a block of queries at a time so that L = 4,096 fits.

Equations (``cfg`` is the configuration file, parameters the nested dict the
system's checkpoint holds):

- ``x = E[ids]``; layer ``l``: ``h = x + Attn(RMS(x))``,
  ``x' = h + FFN_l(RMS(h))``; ``RMS`` once more after the last layer.
- MLA: ``c_q = RMS(u W_qa)``, ``q = c_q W_qb`` -> heads of ``[nope | rope]``;
  ``u W_kva`` -> ``[c_kv | k_rope]``, ``k_rope`` shared by the heads;
  ``RMS(c_kv) W_kvb`` -> per head ``[k_nope | v]``; RoPE (interleaved pairs,
  positions 0..L-1) on ``q_rope`` and ``k_rope``;
  ``softmax(q k^T / sqrt(nope + rope) + causal + key-pad) v``; ``W_o``.
- FFN of the first ``first_k_dense_replace`` layers:
  ``W_down(silu(x W_gate) * (x W_up))``.
- FFN of the others: ``s = sigmoid(x W_g)`` over all experts; chosen = top-k
  of ``s + b``; ``w_i = routed_scaling_factor * s_i / sum_chosen s``;
  ``y = Shared(x) + sum_{i chosen and held} w_i Expert_i(x)``. Only the experts
  ``experts_held.first .. first + count - 1`` are here: the rest of the sum is
  another chip's, left out here as in the system.

Departures from the published model, the system's and noted: no multi-token
prediction module and no LM head (the recipe has no token-level loss); the
class and regressor heads read each row's last attended token, without a
pooler; span logits at padded positions are pushed to -1e9.

``forward`` also returns, per expert layer, the experts chosen and the margin
between the k-th and (k+1)-th biased score: top-8 of 256 flips under bf16
rounding of the hidden state wherever that margin is small, so a comparison
may hand the system's choice back in (``routing``) and judge the routing apart.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import loss  # noqa: F401 - the recipe's loss, model-independent

MASK_NEG = -1e9


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(p["scale"])


def _matmul(x, w):
    """Every matrix product of this file (one place to lower the precision
    of, for the readings behind the comparison's limits)."""
    return x @ w


def _mm(x, p):
    return _matmul(x, _f32(p["kernel"]))


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


def _rope(x, theta):
    """Each interleaved pair ``(x[2i], x[2i+1])`` turned by the angle
    ``position * theta ** (-2i / d)``."""
    L, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape((1, L) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape)


def _score_scale(d_qk):
    return 1.0 / math.sqrt(d_qk)


def _causal(rows, L):
    """[len(rows), L]: query ``rows[i]`` may see keys ``0..rows[i]``."""
    return jnp.arange(L)[None, :] <= rows[:, None]


def _attention(p, cfg, u, mask, q_block):
    B, L, _ = u.shape
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _mm(_rms(p["q_a_layer_norm"], _mm(u, p["q_a"]), eps), p["q_b"])
    q = q.reshape(B, L, H, nope + rope)
    kv = _mm(u, p["kv_a"])
    k_v = _mm(_rms(p["kv_a_layer_norm"], kv[..., :rank], eps), p["kv_b"])
    k_v = k_v.reshape(B, L, H, nope + d_v)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_rope = _rope(kv[..., rank:], theta)[:, :, None, :]
    k = jnp.concatenate(
        [k_v[..., :nope], jnp.broadcast_to(k_rope, (B, L, H, rope))], -1)
    v = k_v[..., nope:]
    scale = _score_scale(nope + rope)
    block = min(q_block, L)
    assert L % block == 0, (L, block)

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        allowed = _causal(rows, L)[None, None] & (mask[:, None, None, :] > 0)
        probs = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, L, block))   # [n, B, blk, H, d]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, L, H * d_v)
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
        picked = picked / picked.sum(-1, keepdims=True)
    weights = picked * cfg["routed_scaling_factor"]
    y = _swiglu(p["shared_expert"], x)
    experts = p["experts"]
    for e in range(count):          # every held expert over every token
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(_matmul(x, _f32(experts["gate"][e]))) * _matmul(
            x, _f32(experts["up"][e]))
        y = y + w_e[..., None] * _matmul(hidden, _f32(experts["down"][e]))
    return y, own, margin


def forward(params, cfg: dict, input_ids, attention_mask, token_type_ids=None,
            *, routing=None, q_block: int = 256):
    """``(predictions, {'chosen': [...], 'margin': [...], 'router_input':
    [...]})``: the QA heads' outputs in float32 and, per expert layer, the
    reference's own top-k [B, L, K], its margin [B, L] and the state its
    router read [B, L, hidden]. ``routing``: one [B, L, K] choice per expert
    layer to use instead of the top-k."""
    del token_type_ids      # the model has no such table
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(input_ids),
                        jnp.asarray(attention_mask), routing, q_block)


def _forward(params, cfg, ids, mask, routing, q_block):
    t = params["transformer"]
    eps = cfg["rms_norm_eps"]
    x = _f32(t["word_embeddings"]["embedding"])[ids]
    chosen, margins, states = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        layer = t[f"layer_{i}"]
        h = x + _attention(layer["attention"], cfg,
                           _rms(layer["input_layer_norm"], x, eps), mask,
                           q_block)
        u = _rms(layer["post_attention_layer_norm"], h, eps)
        if i < cfg["first_k_dense_replace"]:
            y = _swiglu(layer["mlp"], u)
        else:
            given = None if routing is None else jnp.asarray(
                routing[len(chosen)])
            y, own, margin = _expert_layer(layer["mlp"], cfg, u, given)
            chosen.append(own)
            margins.append(margin)
            states.append(u)
        x = h + y
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
                   "router_input": states}

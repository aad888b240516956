"""The plain reference of the ``mellum`` trunk (Mellum2-12B-A2.5B-Instruct,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct) with the
recipe's QA heads and loss, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernels, no bf16, nothing
imported from ``ml_recipe_tpu``. Written from the equations below, not from
the program: the mask is an explicit ``[L, L]`` band built from ``i - j``,
grouped key/value heads are repeated with ``jnp.repeat``, the experts are a
dense mixture (every held expert over every token, weighed by a one-hot sum),
and attention is the full masked softmax a block of queries at a time so that
L = 8,192 fits. RMSNorm, the recipe's loss and the span mask's constant are
``reference_lfm2``'s.

Equations (``cfg`` is the configuration file, parameters the nested dict the
system's checkpoint holds; every projection bias-free; ``x`` [L, hidden]):

- ``x = E[ids]``; layer ``l``: ``h = x + Attn_kind(RMS(x))``,
  ``y = h + MoE(RMS(h))`` (pre-norm, eps ``rms_norm_eps``, a learned scale);
  ``RMS`` once more after the last layer. ``kind = layer_types[l]``.
- ``q = W_q u`` (``num_attention_heads`` heads of ``head_dim``), ``k = W_k
  u``, ``v = W_v u`` (``num_key_value_heads``); an RMSNorm over each head's
  width on q and on k (one learned scale each, shared by the heads), then the
  rotation over half-split pairs ``(x[i], x[i + d/2])``; query head ``h``
  reads key/value head ``h // group``; ``o = W_o concat_h softmax(q_h
  k_{h // group}^T / sqrt(d) + M_kind) v_{h // group}``.
- ``M_kind[i, j] = 0`` where key ``j`` is permitted, else ``-inf``.
  ``full_attention``: ``j <= i``. ``sliding_attention``: ``0 <= i - j <
  sliding_window`` (a query sees itself and the ``sliding_window - 1`` keys
  before it). Padded keys are never permitted.
- Rotation, by ``rope_parameters[kind]``. ``default``: angle ``p theta ** (-2i
  / d)``. ``yarn``: ``f_i = theta ** (-2i / d)``; ``c(n) = d ln(original /
  (2 pi n)) / (2 ln theta)``; ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``; ``r_i = clip((i - low) / (high - low), 0, 1)``;
  ``f'_i = (1 - r_i) f_i + r_i f_i / factor``; cos and sin of ``p f'_i``,
  each times ``attention_factor``, on q and on k.
- ``MoE(u) = sum_{e in top-k(s)} (s_e / sum_{e' in top-k} s_e') Expert_e(u)``,
  ``s = softmax(W_r u)`` over ALL experts, ``Expert_e(u) = W_down,e
  (silu(W_gate,e u) * W_up,e u)``; no shared expert, no selection bias, no
  scaling factor. Only the experts ``experts_held.first .. first + count - 1``
  are here: the rest of the sum is another chip's, left out here as in the
  system.

Departures from the published model, the system's and noted: no LM head and
no multi-token-prediction module (the recipe has no token-level loss); the
class and regressor heads read each row's last attended token; span logits
at padded positions are pushed to -1e9.

``forward`` also returns, per expert layer, the experts chosen and the margin
between the k-th and (k+1)-th router LOGIT (the order of the probabilities is
the logits', and a logit's scale does not shrink with the number of experts),
and per attention layer the q, k and v its core read (after norm and
rotation) and what it wrote: top-8 of 64 flips under bf16 rounding of the
hidden state wherever that margin is small, so a comparison may hand the
system's choice back in (``routing``) and judge the routing apart.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference_lfm2 import MASK_NEG, _f32, _rms, loss  # noqa: F401


def _matmul(x, w):
    """Every matrix product of the layers (one place to lower the precision
    of, for the readings behind the comparison's limits)."""
    return x @ w


def _softmax(scores):
    """The attention core's softmax over permitted keys (the one place to
    lower it, for the same readings)."""
    return jax.nn.softmax(scores, axis=-1)


def _mm(x, p):
    return _matmul(x, _f32(p["kernel"]))


def rotation(cfg: dict, kind: str, d: int):
    """``(frequencies [d / 2], factor)`` of a layer of ``kind``, from the
    published ``rope_parameters``."""
    rope = cfg["rope_parameters"][kind]
    theta = float(rope["rope_theta"])
    own = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope["rope_type"] == "default":
        return own, 1.0
    assert rope["rope_type"] == "yarn", rope

    def pair_turning(n):
        return d * math.log(rope["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return ((1.0 - ramp) * own + ramp * own / rope["factor"],
            float(rope["attention_factor"]))


def rope_half_split(x, frequencies, factor):
    """``x * cos + rotate_half(x) * sin`` over ``x`` [B, L, H, d]: the pair
    ``(x[i], x[i + d/2])`` turned by ``position * frequencies[i]``, cos and
    sin each times ``factor``."""
    L, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * frequencies[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * jnp.cos(angle) + rotated * jnp.sin(angle)) * factor


def attention_core(q, k, v, mask, window, q_block: int = 128):
    """``softmax(q k^T / sqrt(d) + M) v`` over ``q`` [B, L, H, d] and ``k``,
    ``v`` [B, L, H_kv, d] in f32, ``M`` the explicit band: ``0 <= i - j <
    window`` (``window`` None: ``j <= i``) on real keys. A block of query
    rows at a time."""
    B, L, H, d = q.shape
    q, k, v = _f32(q), _f32(k), _f32(v)
    k, v = (jnp.repeat(x, H // k.shape[2], axis=2) for x in (k, v))
    block = min(q_block, L)
    assert L % block == 0, (L, block)

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        distance = rows[:, None] - jnp.arange(L)[None, :]       # i - j
        band = distance >= 0
        if window is not None:
            band = band & (distance < window)
        allowed = band[None, None] & (mask[:, None, None, :] > 0)
        probs = _softmax(jnp.where(allowed, s, -jnp.inf))
        # a padded query further than the window from the last real key sees
        # nothing: its row is zero, not the softmax's NaN
        probs = jnp.where(allowed.any(-1, keepdims=True), probs, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, L, block))   # [n, B, blk, H, d]
    return jnp.moveaxis(ctx, 0, 1).reshape(B, L, H, d)


def _attention(p, cfg, kind, u, mask, q_block):
    """``(W_o core, (q, k, v), core)`` of one attention layer."""
    B, L, _ = u.shape
    H, H_kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    turn = rotation(cfg, kind, d)
    q = _mm(u, p["q"]).reshape(B, L, H, d)
    k = _mm(u, p["k"]).reshape(B, L, H_kv, d)
    v = _mm(u, p["v"]).reshape(B, L, H_kv, d)
    q = rope_half_split(_rms(p["q_layer_norm"], q, eps), *turn)
    k = rope_half_split(_rms(p["k_layer_norm"], k, eps), *turn)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    ctx = attention_core(q, k, v, mask, window, q_block)
    return _mm(ctx.reshape(B, L, H * d), p["output"]), (q, k, v), ctx


def route(p, cfg, x):
    """``(chosen [..., K], margin [...], scores [..., E])`` of router ``p``
    (``kernel``) on states ``x``: the top-k of the softmax over all experts,
    and the k-th less the (k+1)-th LOGIT."""
    with jax.default_matmul_precision("highest"):
        K = cfg["num_experts_per_tok"]
        logits = _matmul(_f32(x), _f32(p["kernel"]))
        top, chosen = jax.lax.top_k(logits, K + 1)
        return (chosen[..., :K], top[..., K - 1] - top[..., K],
                jax.nn.softmax(logits, axis=-1))


def expert_layer(p, cfg, x, chosen=None, held=None):
    """``(y, chosen, margin)``; ``chosen`` [B, L, K] overrides the top-k (the
    weights still come from this function's own scores); ``held``: another
    share than the configuration's ``experts_held`` (``first``, ``count``),
    with ``p['experts']`` that share's weights."""
    held = held or cfg["experts_held"]
    first, count = held["first"], held["count"]
    own, margin, scores = route(p["router"], cfg, x)
    if chosen is None:
        chosen = own
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
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
    [...], 'attention': [...]})``: the QA heads' outputs in float32 and, per
    expert layer, the reference's own top-k [B, L, K], its margin [B, L] and
    the state its router read [B, L, hidden]; per attention layer ``((q, k,
    v), core)``: what its core read and wrote. ``routing``: one [B, L, K]
    choice per expert layer to use instead of the top-k."""
    del token_type_ids      # the model has no such table
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(input_ids),
                        jnp.asarray(attention_mask), routing, q_block)


def _forward(params, cfg, ids, mask, routing, q_block):
    t = params["transformer"]
    eps = cfg["rms_norm_eps"]
    x = _f32(t["word_embeddings"]["embedding"])[ids]
    chosen, margins, states, cores = [], [], [], []
    for i, kind in enumerate(cfg["layer_types"]):
        layer = t[f"layer_{i}"]
        assert kind in ("sliding_attention", "full_attention"), kind
        wrote, read, core = _attention(
            layer["attention"], cfg, kind,
            _rms(layer["input_layer_norm"], x, eps), mask, q_block)
        cores.append((read, core))
        h = x + wrote
        u = _rms(layer["post_attention_layer_norm"], h, eps)
        given = None if routing is None else jnp.asarray(routing[i])
        y, own, margin = expert_layer(layer["mlp"], cfg, u, given)
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
                   "router_input": states, "attention": cores}

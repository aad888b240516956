"""The plain reference: post-LN BERT encoder (Devlin et al. 2018) with the
recipe's QA heads and loss, in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. No kernels, no bf16, no
dropout, nothing imported from ``ml_recipe_tpu``. Parameters come as the
nested dict of arrays the system's checkpoint holds (``transformer/
embeddings|layer_<i>|pooler``, ``position_outputs``, ``classifier``,
``reg_start``, ``reg_end``; ``kernel`` [in, out], ``bias``, ``scale``,
``embedding``).

Departure from the paper, the system's own and noted: span logits at padded
positions are pushed to -1e9 so that a fixed-shape row's argmax stays inside
the row (``models/qa_model.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MASK_NEG = -1e9


def _dense(p, x):
    return x @ jnp.asarray(p["kernel"], jnp.float32) + jnp.asarray(
        p["bias"], jnp.float32)


def _layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * jnp.asarray(
        p["scale"], jnp.float32) + jnp.asarray(p["bias"], jnp.float32)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def forward(params, cfg: dict, input_ids, attention_mask, token_type_ids):
    """``{'start_class','end_class' [B, L]; 'cls' [B, 5]; 'start_reg',
    'end_reg' [B]}`` in float32."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(input_ids),
                        jnp.asarray(attention_mask),
                        jnp.asarray(token_type_ids))


def _forward(params, cfg, ids, mask, types):
    t = params["transformer"]
    emb = t["embeddings"]
    eps = cfg.get("layer_norm_eps", 1e-12)
    heads = cfg["num_attention_heads"]
    B, L = ids.shape
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = (f32(emb["word_embeddings"]["embedding"])[ids]
         + f32(emb["position_embeddings"]["embedding"])[jnp.arange(L)][None]
         + f32(emb["token_type_embeddings"]["embedding"])[types])
    x = _layer_norm(emb["layer_norm"], x, eps)
    key_bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        layer = t[f"layer_{i}"]
        att = layer["attention"]
        split = lambda y: y.reshape(B, L, heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
        q, k, v = (split(_dense(att[n], x)) for n in ("query", "key", "value"))
        scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(scores + key_bias, axis=-1)
        ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(B, L, -1)
        x = _layer_norm(att["layer_norm"], x + _dense(att["output"], ctx), eps)
        mlp = layer["mlp"]
        y = _dense(mlp["output"], _gelu(_dense(mlp["intermediate"], x)))
        x = _layer_norm(mlp["layer_norm"], x + y, eps)
    pooled = jnp.tanh(_dense(t["pooler"], x[:, 0]))
    span = _dense(params["position_outputs"], x)
    pad = (1 - mask).astype(jnp.float32) * MASK_NEG
    return {
        "start_class": span[..., 0] + pad,
        "end_class": span[..., 1] + pad,
        "cls": _dense(params["classifier"], pooled),
        "start_reg": jax.nn.sigmoid(_dense(params["reg_start"], pooled))[..., 0],
        "end_reg": jax.nn.sigmoid(_dense(params["reg_end"], pooled))[..., 0],
    }


def _span_ce(logits, targets):
    """Mean negative log-likelihood over rows whose target is not -1."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = targets != -1
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _smooth_kl(logits, targets, alpha, n_classes=5):
    """KL(batchmean) against the smoothed one-hot target: ``1 - alpha`` on
    the class, ``alpha / (n_classes - 1)`` elsewhere."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    dist = jnp.full(logits.shape, alpha / (n_classes - 1), jnp.float32)
    dist = dist.at[jnp.arange(logits.shape[0]), targets].set(1.0 - alpha)
    t_log_t = jnp.where(dist > 0, dist * jnp.log(dist), 0.0)
    return jnp.mean(jnp.sum(t_log_t - dist * logp, axis=-1))


def loss(preds: dict, labels: dict, *, smooth_alpha: float) -> jnp.ndarray:
    """The recipe's loss with every head weight 1 (``config/test_bert.cfg``):
    span cross-entropies, position-regressor squared errors, smoothed
    5-class KL."""
    mse = lambda a, b: jnp.mean((a - jnp.asarray(b, jnp.float32)) ** 2)  # noqa: E731
    return (
        _span_ce(preds["start_class"], jnp.asarray(labels["start_class"]))
        + _span_ce(preds["end_class"], jnp.asarray(labels["end_class"]))
        + mse(preds["start_reg"], labels["start_reg"])
        + mse(preds["end_reg"], labels["end_reg"])
        + _smooth_kl(preds["cls"], jnp.asarray(labels["cls"]), smooth_alpha)
    )


def answerability(preds: dict) -> dict:
    """What the serving forward reduces each chunk to (arXiv 1901.08634):
    ``score = max(start) + max(end) - (start[0] + end[0])``, with the
    maxima themselves."""
    start, end = preds["start_class"], preds["end_class"]
    return {
        "scores": (start.max(-1) + end.max(-1)) - (start[:, 0] + end[:, 0]),
        "start_max": start.max(-1), "end_max": end.max(-1),
    }

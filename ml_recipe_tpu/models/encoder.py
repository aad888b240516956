"""First-party Flax BERT/RoBERTa encoder.

Replaces the HF ``BertModel``/``RobertaModel`` trunk the reference loads in
``modules/model/model/model.py:20-25``. Architecture is the standard
post-layer-norm BERT stack; differences from a naive port are TPU-driven:

- activations run in ``cfg.dtype`` (bf16 by default) while params stay f32 —
  the native replacement for Apex AMP (reference trainer.py:128-133);
- attention goes through ``ops.dot_product_attention`` so the Pallas flash
  kernel can be swapped in without touching the module;
- optional per-layer rematerialisation (``jax.checkpoint``) trades FLOPs for
  HBM on long-sequence configs;
- no data-dependent Python control flow — the whole forward is one traced
  XLA program.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.layer_norm import layer_norm
from .config import EncoderConfig


class FusedLayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm`` backed by the one-pass Pallas backward
    (ops/layer_norm.py). Same param names/shapes ('scale'/'bias', [C], f32)
    so checkpoints are interchangeable between ``ln_impl`` settings."""

    epsilon: float = 1e-12
    dtype: jnp.dtype = jnp.float32
    impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        C = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (C,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (C,), jnp.float32)
        return layer_norm(x, scale, bias, eps=self.epsilon, dtype=self.dtype,
                          impl=self.impl)


def _ln(cfg: EncoderConfig, dtype, ln_impl: str, name: str):
    """LayerNorm factory: 'xla' keeps flax's nn.LayerNorm (bit-identical to
    every recorded baseline); anything else routes through the fused op."""
    if ln_impl == "xla":
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, name=name, dtype=dtype)
    return FusedLayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                          impl=ln_impl, name=name)


def _dense(quantize: str, features: int, *, name: str, dtype):
    """Dense factory for the matmul-dominant projections: 'off' keeps
    flax's nn.Dense bit-identically (params AND arithmetic — the default
    serving/training path is untouched); 'int8' swaps in QuantDense
    (quant/layers.py) under the SAME module name, so a converted checkpoint
    tree (quant/quantize.py) lands on exactly these params."""
    if quantize == "int8":
        from ..quant.layers import QuantDense

        return QuantDense(features, name=name, dtype=dtype)
    if quantize not in (None, "off"):
        raise ValueError(
            f"quantize must be 'off' or 'int8', got {quantize!r}"
        )
    return nn.Dense(features, name=name, dtype=dtype)


class Embeddings(nn.Module):
    cfg: EncoderConfig
    dtype: jnp.dtype = jnp.float32
    ln_impl: str = "xla"

    @nn.compact
    def __call__(self, input_ids, token_type_ids, *, deterministic: bool,
                 position_ids=None):
        cfg = self.cfg

        word = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="word_embeddings",
                        dtype=self.dtype)(input_ids)

        L = input_ids.shape[-1]
        if L + cfg.position_offset > cfg.max_position_embeddings:
            # fail at TRACE time (L is static) instead of letting the
            # clip-mode embedding gather silently hand every position past
            # the table its last row — a model that trains and benches fine
            # with no positional signal beyond the table (review r5).
            # Packed position_ids are per-segment (each < its segment
            # length <= L), so the same L-based bound covers them.
            raise ValueError(
                f"sequence length {L} (+offset {cfg.position_offset}) "
                f"exceeds max_position_embeddings="
                f"{cfg.max_position_embeddings}; widen the position table "
                f"(--max_position_embeddings) for long-context runs"
            )
        if position_ids is None:
            positions = jnp.arange(L, dtype=jnp.int32) + cfg.position_offset
            pos = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                           name="position_embeddings", dtype=self.dtype)(positions)[None, :, :]
        else:
            # sequence packing: positions reset to 0 at every segment
            # boundary, so each packed chunk sees exactly the positional
            # signal it would see unpacked
            positions = position_ids.astype(jnp.int32) + cfg.position_offset
            pos = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                           name="position_embeddings", dtype=self.dtype)(positions)

        if cfg.type_vocab_size > 1:
            typ = nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                           name="token_type_embeddings", dtype=self.dtype)(token_type_ids)
        else:
            # RoBERTa has a single segment type; keep the param for checkpoint
            # parity but index it with zeros.
            typ = nn.Embed(1, cfg.hidden_size, name="token_type_embeddings",
                           dtype=self.dtype)(jnp.zeros_like(token_type_ids))

        x = word + pos + typ
        x = _ln(cfg, self.dtype, self.ln_impl, "layer_norm")(x)
        x = nn.Dropout(cfg.hidden_dropout_prob)(x, deterministic=deterministic)
        return x


class SelfAttention(nn.Module):
    cfg: EncoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    mesh: Any = None  # required by impl='ring' (sequence parallelism)
    ln_impl: str = "xla"
    quantize: str = "off"  # int8 serving path (quant/): QKV + out proj

    @nn.compact
    def __call__(self, hidden, mask, *, deterministic: bool,
                 segment_ids=None):
        cfg = self.cfg
        B, L, H = hidden.shape

        def heads(name):
            y = _dense(self.quantize, cfg.hidden_size, name=name,
                       dtype=self.dtype)(hidden)
            return y.reshape(B, L, cfg.num_heads, cfg.head_dim)

        q, k, v = heads("query"), heads("key"), heads("value")

        dropout_rng = None
        if not deterministic and cfg.attention_probs_dropout_prob > 0:
            # "attention_dropout" is this model's rng name for the attention
            # mask stream (default: "dropout"'s). A caller whose "dropout"
            # key differs from shard to shard of the batch brings the SHARED
            # key here, and ops/attention.py keeps the masks those of the
            # unsharded call
            dropout_rng = self.make_rng(
                "attention_dropout" if self.has_rng("attention_dropout")
                else "dropout")

        ctx = dot_product_attention(
            q, k, v, mask,
            dropout_rate=0.0 if deterministic else cfg.attention_probs_dropout_prob,
            dropout_rng=dropout_rng,
            dtype=self.dtype,
            impl=self.attention_impl,
            mesh=self.mesh,
            segment_ids=segment_ids,
        )
        ctx = ctx.reshape(B, L, cfg.hidden_size)

        out = _dense(self.quantize, cfg.hidden_size, name="output",
                     dtype=self.dtype)(ctx)
        out = nn.Dropout(cfg.hidden_dropout_prob)(out, deterministic=deterministic)
        return _ln(cfg, self.dtype, self.ln_impl, "layer_norm")(hidden + out)


class FeedForward(nn.Module):
    cfg: EncoderConfig
    dtype: jnp.dtype = jnp.float32
    ln_impl: str = "xla"
    quantize: str = "off"

    @nn.compact
    def __call__(self, hidden, *, deterministic: bool):
        cfg = self.cfg
        y = _dense(self.quantize, cfg.intermediate_size, name="intermediate",
                   dtype=self.dtype)(hidden)
        y = nn.gelu(y, approximate=False)
        y = _dense(self.quantize, cfg.hidden_size, name="output",
                   dtype=self.dtype)(y)
        y = nn.Dropout(cfg.hidden_dropout_prob)(y, deterministic=deterministic)
        return _ln(cfg, self.dtype, self.ln_impl, "layer_norm")(hidden + y)


class EncoderLayer(nn.Module):
    cfg: EncoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    mesh: Any = None
    ln_impl: str = "xla"
    quantize: str = "off"

    @nn.compact
    def __call__(self, hidden, mask, deterministic: bool = True,
                 segment_ids=None):
        hidden = SelfAttention(self.cfg, self.dtype, self.attention_impl,
                               self.mesh, self.ln_impl,
                               quantize=self.quantize, name="attention")(
                               hidden, mask, deterministic=deterministic,
                               segment_ids=segment_ids)
        hidden = FeedForward(self.cfg, self.dtype, self.ln_impl,
                             quantize=self.quantize, name="mlp")(
            hidden, deterministic=deterministic
        )
        return hidden


class TransformerEncoder(nn.Module):
    """BERT/RoBERTa trunk: returns (sequence_output, pooled_output)."""

    cfg: EncoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    remat: bool = False
    mesh: Any = None
    ln_impl: str = "xla"
    # 'int8': serving-only post-training quantization (quant/) — every
    # matmul-dominant Dense (QKV/attn-out/FFN/pooler) runs the fused int8
    # path; 'off' (default) is bit-identical to the historical model
    quantize: str = "off"

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask: Optional[jnp.ndarray] = None,
        token_type_ids: Optional[jnp.ndarray] = None,
        *,
        deterministic: bool = True,
        position_ids: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        segment_starts: Optional[jnp.ndarray] = None,
    ):
        cfg = self.cfg
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)

        hidden = Embeddings(cfg, self.dtype, self.ln_impl, name="embeddings")(
            input_ids, token_type_ids, deterministic=deterministic,
            position_ids=position_ids,
        )

        layer_cls = EncoderLayer
        if self.remat:
            layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))

        for i in range(cfg.num_layers):
            hidden = layer_cls(cfg, self.dtype, self.attention_impl, self.mesh,
                               self.ln_impl, quantize=self.quantize,
                               name=f"layer_{i}")(
                               hidden, attention_mask, deterministic,
                               segment_ids)

        if segment_starts is None:
            pool_src = hidden[:, 0]
        else:
            # sequence packing: one pooled vector PER SEGMENT, from each
            # segment's own [CLS] row ([B, S, H]; absent segments gather
            # row 0 and are masked downstream). The pooler params are the
            # same Dense — a single-segment row starting at 0 reproduces
            # the unpacked pooled output exactly.
            pool_src = jnp.take_along_axis(
                hidden, segment_starts[..., None].astype(jnp.int32), axis=1
            )
        pooled = _dense(self.quantize, cfg.hidden_size, name="pooler",
                        dtype=self.dtype)(pool_src)
        pooled = jnp.tanh(pooled)

        return hidden, pooled

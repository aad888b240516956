"""Encoder architecture configs.

The reference delegates architecture to HF ``BertModel``/``RobertaModel``
(model/model.py:9-10,20-25), exposing only dropout/layer-norm knobs through its
model parser (parser.py:70-74). Here the encoder is first-party, so the full
architecture is explicit; presets cover the reference's supported checkpoints
(``bert-base-uncased``/``roberta-base``, parser.py:66-68) plus the large
variants.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    model_type: str = "bert"  # 'bert' | 'roberta'
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    # RoBERTa reserves position ids 0/1 (pad handling); real positions start at 2.
    position_offset: int = 0
    num_labels: int = 5

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """A pre-norm causal trunk of latent-attention (MLA) layers whose FFN is
    dense SwiGLU in the leading layers and a routed expert layer with a shared
    expert after them (``models/mla_moe.py``). Keys follow the published
    ``config.json`` of the ``joyai_llm_flash`` / DeepSeek-V3 family.

    ``n_routed_experts`` is the router's width; ``experts_first`` /
    ``experts_held`` say which of them THIS process holds (an expert-parallel
    deployment's share): the layer routes over all of them and computes its
    own experts' part of the result. The published model has no dropout; the
    two rates exist because the QA heads read them, and stay 0."""

    model_type: str = "joyai_llm_flash"
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168       # the leading dense layers' FFN
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    experts_first: int = 0
    experts_held: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    pad_token_id: int = 0
    hidden_dropout_prob: float = 0.0
    attention_probs_dropout_prob: float = 0.0
    num_labels: int = 5
    # the vocabulary file's format: rows are ready-made ids, words are
    # WordPiece-style entries written from a seed
    tokenizer_family: str = "bert"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


MODEL_PRESETS = {
    # google/bert_uncased_L-2_H-128_A-2 dims — CI smoke runs and CPU-mesh
    # integration tests; shares the full bert vocab so any bert tokenizer ids
    # stay in range
    "bert-tiny": EncoderConfig(
        model_type="bert", vocab_size=30522, hidden_size=128, num_layers=2,
        num_heads=2, intermediate_size=512,
    ),
    "bert-base-uncased": EncoderConfig(
        model_type="bert", vocab_size=30522, hidden_size=768, num_layers=12,
        num_heads=12, intermediate_size=3072,
    ),
    "bert-large-uncased": EncoderConfig(
        model_type="bert", vocab_size=30522, hidden_size=1024, num_layers=24,
        num_heads=16, intermediate_size=4096,
    ),
    "roberta-base": EncoderConfig(
        model_type="roberta", vocab_size=50265, hidden_size=768, num_layers=12,
        num_heads=12, intermediate_size=3072, max_position_embeddings=514,
        type_vocab_size=1, pad_token_id=1, position_offset=2, layer_norm_eps=1e-5,
    ),
    "roberta-large": EncoderConfig(
        model_type="roberta", vocab_size=50265, hidden_size=1024, num_layers=24,
        num_heads=16, intermediate_size=4096, max_position_embeddings=514,
        type_vocab_size=1, pad_token_id=1, position_offset=2, layer_norm_eps=1e-5,
    ),
    # One chip's share of JoyAI-LLM-Flash (48B-A2.7B) under a deployment in
    # which 16 chips share each layer: 16 of the 256 routed experts, 1/8 of
    # the vocabulary, the leading dense layer and 4 expert layers (the other
    # 35 lie on further chips as pipeline stages). Every width is the
    # published one (perfbench/configs/joyai-llm-flash-ep16.json).
    "joyai-llm-flash-ep16": DecoderConfig(
        vocab_size=16160, num_layers=5, experts_first=0, experts_held=16,
    ),
    # the same rank structure at a size for the CPU tests
    "joyai-tiny": DecoderConfig(
        vocab_size=16160, hidden_size=64, num_layers=3, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, experts_first=2,
        experts_held=4, num_experts_per_tok=2,
    ),
}


def resolve_model_config(model_params, *, num_labels: int = 5):
    """Build the encoder config from parsed model params (init.py:51-82 parity:
    dropout/layer-norm overrides are applied on top of the preset). A
    ``DecoderConfig`` preset carries every size and rate itself."""
    name = getattr(model_params, "model", "bert-base-uncased")
    preset = MODEL_PRESETS[name]
    if isinstance(preset, DecoderConfig):
        return dataclasses.replace(preset, num_labels=num_labels)
    # long-context: an explicit --max_position_embeddings widens the
    # position table past the preset's (positions beyond it are a
    # trace-time error in Embeddings, never a silent clamp)
    mpe = getattr(model_params, "max_position_embeddings", None) \
        or preset.max_position_embeddings
    return dataclasses.replace(
        preset,
        hidden_dropout_prob=getattr(model_params, "hidden_dropout_prob", preset.hidden_dropout_prob),
        attention_probs_dropout_prob=getattr(
            model_params, "attention_probs_dropout_prob", preset.attention_probs_dropout_prob
        ),
        layer_norm_eps=getattr(model_params, "layer_norm_eps", preset.layer_norm_eps),
        max_position_embeddings=mpe,
        num_labels=num_labels,
    )

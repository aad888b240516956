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
from typing import Optional, Tuple, Union


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
    """A causal trunk (``models/mla_moe.py``) whose layers each pick an
    operator (latent attention, grouped-query attention with or without a
    sliding window, a gated short convolution or gated delta-rule linear
    attention) and whose FFN is dense
    SwiGLU in the leading layers and a routed expert layer, with or without a
    shared expert, after them. Keys follow the published ``config.json`` of
    the ``joyai_llm_flash`` / DeepSeek-V3 family and, for what that family
    lacks, of ``lfm2_moe`` (``layer_types``, ``num_kv_heads``, ``head_dim``,
    ``conv_L_cache``), ``olmo_hybrid`` (the ``linear_*`` keys) and ``mellum``
    (``sliding_window``; its per-kind ``rope_parameters`` are the ``yarn_*``
    keys here).

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
    rope_theta: Optional[float] = 32000000.0  # None: nothing is rotated
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    pad_token_id: int = 0
    hidden_dropout_prob: float = 0.0
    attention_probs_dropout_prob: float = 0.0
    num_labels: int = 5
    # the vocabulary file's format: rows are ready-made ids, words are
    # WordPiece-style entries written from a seed
    tokenizer_family: str = "bert"
    # each layer's operator: "mla" (the ranks above), "full_attention"
    # (grouped-query heads, the five keys below), "sliding_attention" (the
    # same heads under ``sliding_window``), "conv" (a gated short convolution
    # of ``conv_L_cache`` taps) or "linear_attention" (the gated delta rule,
    # the ``linear_*`` keys); () is "mla" in every layer
    layer_types: Tuple[str, ...] = ()
    num_kv_heads: int = 0               # 0: as many as query heads
    head_dim: int = 0                   # 0: hidden_size // num_heads
    # an RMSNorm on q and on k: True over each head's width, "whole" over
    # the whole projection's before it is split into heads
    qk_norm: Union[bool, str] = False
    rope_interleaved: bool = True       # pairs (x[2i], x[2i+1]); else
    #                                     (x[i], x[i + d/2])
    conv_L_cache: int = 3
    # where a layer's two norms stand: on the operator's and the FFN's INPUT
    # (``h = x + Op(norm(x))``) or, reordered, on their OUTPUT (``h = x +
    # norm(Op(x))``)
    norm_after: bool = False
    # linear attention: heads (as many key as value heads), a key's and a
    # value's width a head, the taps of the causal convolution on q, k and v;
    # ``allow_neg_eigval``: the write strength is 2 sigmoid and not sigmoid
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # added to the chosen scores' sum before it divides them
    norm_topk_eps: float = 1e-20
    # the seeded selection bias's standard deviation, in SCORE space (sigmoid
    # outputs); 0: ``initializer_range``
    expert_bias_range: float = 0.0
    # a sliding_attention layer's query sees itself and the
    # ``sliding_window - 1`` keys before it
    sliding_window: int = 0
    # YaRN on the full_attention layers' rotation (the sliding layers keep the
    # plain one): pair frequencies blended between ``theta ** (-2i / d)`` and
    # that over ``yarn_factor`` across the pairs that turn ``yarn_beta_fast``
    # to ``yarn_beta_slow`` times in ``yarn_original_positions`` positions, cos
    # and sin times ``yarn_attention_factor`` (0: ``0.1 ln(factor) + 1``);
    # factor 0: no scaling
    yarn_factor: float = 0.0
    yarn_original_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0
    # the router's scores over all experts: "sigmoid" (with the selection
    # ``bias``) or "softmax" (no bias parameter)
    scoring_func: str = "sigmoid"
    # the embedding's standard deviation at initialisation; 0:
    # ``initializer_range``. At unit RMS (1.0) a token's own row, not the
    # attention layers' average of a window's values (the same for every
    # query of it, and at seeded weights several times an embedding of 0.02),
    # decides what the routers read
    embedding_range: float = 0.0

    def __post_init__(self):
        kinds = set(self.layer_types) - {"mla", "full_attention",
                                         "sliding_attention", "conv",
                                         "linear_attention"}
        if kinds or (self.layer_types
                     and len(self.layer_types) != self.num_layers):
            raise ValueError(
                f"layer_types {self.layer_types} must name one of mla / "
                f"full_attention / sliding_attention / conv / "
                f"linear_attention for each of {self.num_layers} layers")
        if self.windows and self.sliding_window < 1:
            raise ValueError(
                "a sliding_attention layer needs sliding_window >= 1")
        if self.yarn_factor and (self.yarn_original_positions < 1
                                 or self.rope_theta is None):
            raise ValueError(
                "yarn_factor needs yarn_original_positions and a rope_theta")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r} must be "
                             f"sigmoid or softmax")
        if "linear_attention" in self.layer_types and min(
                self.linear_num_heads, self.linear_key_head_dim,
                self.linear_value_head_dim, self.linear_conv_kernel_dim) < 1:
            raise ValueError(
                "a linear_attention layer needs linear_num_heads, "
                "linear_key_head_dim, linear_value_head_dim and "
                "linear_conv_kernel_dim")
        if self.qk_norm not in (False, True, "whole"):
            raise ValueError(f"qk_norm {self.qk_norm!r} must be False, True "
                             f"(each head's width) or 'whole' (the whole "
                             f"projection's)")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def operator(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else "mla"

    @property
    def routes(self) -> bool:
        """Some layer's FFN is a routed expert layer."""
        return self.first_k_dense_replace < self.num_layers

    @property
    def scans(self) -> bool:
        """Some layer's operator is the gated delta rule."""
        return "linear_attention" in self.layer_types

    @property
    def windows(self) -> bool:
        """Some layer's attention runs under the sliding window."""
        return "sliding_attention" in self.layer_types


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
    # One chip's share of LFM2-8B-A1B (8.3B-A1.5B) under a deployment in which
    # 4 chips share each layer: 8 of the 32 routed experts, 1/4 of the
    # vocabulary, the leading dense layer and one whole period of the layer
    # pattern (published layers 1..5; the other 19 lie on further chips as
    # pipeline stages). Every width is the published one
    # (perfbench/configs/lfm2-8b-a1b-ep4.json).
    "lfm2-8b-a1b-ep4": DecoderConfig(
        model_type="lfm2_moe", vocab_size=16384, num_layers=5,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_kv_heads=8, head_dim=64, qk_norm=True, rope_interleaved=False,
        moe_intermediate_size=1792, n_routed_experts=32, experts_held=8,
        num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0,
        norm_topk_eps=1e-6, rope_theta=1000000.0, rms_norm_eps=1e-5,
        expert_bias_range=0.002,
    ),
    # both kinds of layer and a dense one at a size for the CPU tests
    "lfm2-tiny": DecoderConfig(
        model_type="lfm2_moe", vocab_size=16384, hidden_size=64,
        num_layers=4, num_heads=4,
        layer_types=("conv", "full_attention", "conv", "full_attention"),
        num_kv_heads=2, head_dim=16, qk_norm=True, rope_interleaved=False,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        experts_first=2, experts_held=4, num_experts_per_tok=2,
        n_shared_experts=0, routed_scaling_factor=1.0, norm_topk_eps=1e-6,
        rope_theta=1000000.0, rms_norm_eps=1e-5, expert_bias_range=0.002,
    ),
    # One pipeline stage of Olmo-Hybrid-7B under a deployment in which 8 chips
    # hold the model as 8 stages of one period each (published layers 0..3:
    # three gated delta-rule layers, then full attention), no layer divided,
    # the embedding's rows spread 8-way over the stages (12,544 of 100,352).
    # Every width is the published one
    # (perfbench/configs/olmo-hybrid-7b-pp8.json).
    "olmo-hybrid-7b-pp8": DecoderConfig(
        model_type="olmo_hybrid", vocab_size=12544, hidden_size=3840,
        num_layers=4, num_heads=30, intermediate_size=11008,
        first_k_dense_replace=4,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        qk_norm="whole", rope_theta=None, norm_after=True,
        linear_num_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    ),
    # the same period, d_k != d_v, at a size for the CPU tests
    "olmo-hybrid-tiny": DecoderConfig(
        model_type="olmo_hybrid", vocab_size=12544, hidden_size=64,
        num_layers=4, num_heads=4, intermediate_size=128,
        first_k_dense_replace=4,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        qk_norm="whole", rope_theta=None, norm_after=True,
        linear_num_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    ),
    # One chip's share of Mellum2-12B-A2.5B-Instruct under a deployment in
    # which 4 chips share each layer: 16 of the 64 routed experts, 1/4 of the
    # vocabulary, one whole period of the layer pattern (published layers
    # 0..3: three sliding-window layers, then full attention with YaRN; the
    # other 24 lie on further chips as pipeline stages). Every width is the
    # published one (perfbench/configs/mellum2-12b-a2.5b-ep4.json).
    "mellum2-12b-a2.5b-ep4": DecoderConfig(
        model_type="mellum", vocab_size=24576, hidden_size=2304,
        num_layers=4, num_heads=32, num_kv_heads=4, head_dim=128,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=1024, qk_norm=True, rope_interleaved=False,
        rope_theta=500000.0, yarn_factor=16.0, yarn_original_positions=8192,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_attention_factor=1.2772588722239782,
        first_k_dense_replace=0, moe_intermediate_size=896,
        n_routed_experts=64, experts_held=16, num_experts_per_tok=8,
        n_shared_experts=0, routed_scaling_factor=1.0, norm_topk_eps=0.0,
        scoring_func="softmax", embedding_range=1.0,
    ),
    # both kinds of layer, a group over 1, a window shorter than its rows and
    # held experts that start at the 2nd, at a size for the CPU tests
    "mellum2-tiny": DecoderConfig(
        model_type="mellum", vocab_size=24576, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=40, qk_norm=True, rope_interleaved=False,
        rope_theta=500000.0, yarn_factor=16.0, yarn_original_positions=64,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_attention_factor=1.2772588722239782,
        intermediate_size=128, first_k_dense_replace=0,
        moe_intermediate_size=32, n_routed_experts=8, experts_first=2,
        experts_held=4, num_experts_per_tok=2, n_shared_experts=0,
        routed_scaling_factor=1.0, norm_topk_eps=0.0, scoring_func="softmax",
        embedding_range=1.0,
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

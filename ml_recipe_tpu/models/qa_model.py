"""Multi-head QA model.

Parity target: reference ``modules/model/model/model.py:13-73``
(``BertForQuestionAnswering``): encoder trunk + four heads —
``position_outputs`` Linear(H,2) giving start/end span logits over tokens,
``classifier`` Dropout+Linear(H,5) on the pooled output, and
``reg_start``/``reg_end`` Linear(H,1)+Sigmoid normalized-position regressors.
Forward returns the same dict contract with keys
``start_class``/``end_class``/``start_reg``/``end_reg``/``cls``.

TPU delta: span logits at padding positions are masked to a large negative
value. The reference pads only to the per-batch max, so stray logits on pad
positions rarely matter there; with static ``max_seq_len`` padding they would
dominate argmax at inference, so masking restores the reference's effective
behaviour under fixed shapes.

Sequence packing (``segment_starts`` given, with ``segment_ids`` /
``position_ids`` from data/packing.collate_packed): the trunk runs with
block-diagonal attention and per-segment positions, and every head becomes
per-SEGMENT — span logits ``[B, S, L]`` (each segment's distribution
confined to its own tokens), cls/regressors from each segment's own [CLS]
row ``[B, S, ...]``. Parameters are identical to the unpacked path, so
checkpoints are interchangeable between packing settings.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from .config import DecoderConfig, EncoderConfig
from .encoder import TransformerEncoder, _dense
from .mla_moe import (REMAT_KEEPS, ROUTING, STAT_SUMS, DecoderTrunk,
                      step_stat_keys, step_stats, unsupported)

QA_OUTPUT_KEYS = ("start_class", "end_class", "start_reg", "end_reg", "cls")

_MASK_NEG = -1e9


class QAModel(nn.Module):
    cfg: Any  # EncoderConfig | DecoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    remat: bool = False
    mesh: Any = None  # required by attention_impl='ring'
    # 'auto'/'fused' = one-pass Pallas LN backward (ops/layer_norm.py).
    # Default stays 'xla': the round-5 on-chip A/B measured the kernel a
    # wash (−0.4%: 732.2 vs 729.2 ms/step on a quiet chip) — it removes
    # the predicted HBM bytes (elementwise 46.6→28.5 ms/step, matmul
    # 468→448) but the custom calls add ~37.5 ms back, because XLA was
    # already fusing the LN work into matmul epilogues. Full decomposition:
    # artifacts/r4/elementwise_floor{,_lnfused}.json + bench_seq512_*.json.
    ln_impl: str = "xla"
    # 'int8': serving-only post-training quantization (quant/) — the
    # encoder's matmul Denses AND the QA heads run the fused int8 path on a
    # converted checkpoint tree (quant.quantize_model). 'off' (default) is
    # bit-identical to the historical model: same modules, same params,
    # same arithmetic. Inference-only — the trainer never sets this.
    quantize: str = "off"

    @property
    def causal_trunk(self) -> bool:
        """The configuration asks for the pre-norm causal decoder trunk
        (``models/mla_moe.py``) and not the post-LN encoder."""
        return isinstance(self.cfg, DecoderConfig)

    @property
    def step_stat_keys(self) -> tuple:
        """Counters the trunk reports beside the loss values every step:
        those its configuration's layers give (an all-dense trunk of
        attention layers gives none)."""
        return step_stat_keys(self.cfg) if self.causal_trunk else ()

    @property
    def step_stat_sums(self) -> tuple:
        """Those of ``step_stat_keys`` that add up over a step's
        micro-batches and chips; the others are ratios and average."""
        return STAT_SUMS if self.causal_trunk else ()

    @property
    def remat_kept_bytes(self) -> int:
        """Bytes the trunk's ``remat`` policy has said "keep" to in the traces
        of this process so far (``mla_moe.KeepDear``): the difference over
        one trace of the step is what a micro-batch's layers keep from their
        forward to their backward pass. The encoder's ``remat`` has no policy
        and keeps a layer's inputs only: 0."""
        return REMAT_KEEPS.kept_bytes if self.causal_trunk else 0

    def apply_with_stats(self, variables, *args, **kwargs):
        """``(predictions, {counter: value})``: ``apply`` with the trunk's
        routing collection collected and reduced to the step's counters."""
        if not self.step_stat_keys:
            return self.apply(variables, *args, **kwargs), {}
        preds, sown = self.apply(variables, *args, mutable=[ROUTING], **kwargs)
        return preds, step_stats(sown[ROUTING])

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        *,
        deterministic: bool = True,
        position_ids=None,
        segment_ids=None,
        segment_starts=None,
    ):
        cfg = self.cfg
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        packed = segment_starts is not None
        if packed and (segment_ids is None or position_ids is None):
            raise ValueError(
                "packed inputs need segment_ids AND position_ids alongside "
                "segment_starts (data/packing.collate_packed emits all "
                "three)"
            )

        if self.causal_trunk:
            unsupported(cfg, quantize=self.quantize, packing=packed)
            trunk = DecoderTrunk(cfg, self.dtype, self.attention_impl,
                                self.remat, self.mesh, name="transformer")
        else:
            trunk = TransformerEncoder(
                cfg, self.dtype, self.attention_impl, self.remat, self.mesh,
                self.ln_impl, quantize=self.quantize, name="transformer")
        sequence_output, pooled_output = trunk(
            input_ids,
            attention_mask=attention_mask,
            token_type_ids=token_type_ids,
            deterministic=deterministic,
            position_ids=position_ids,
            segment_ids=segment_ids,
            segment_starts=segment_starts,
        )

        # span start/end logits over token positions (model.py:30,54-58)
        position_logits = _dense(self.quantize, 2, name="position_outputs",
                                 dtype=self.dtype)(sequence_output)
        start_logits = position_logits[..., 0]
        end_logits = position_logits[..., 1]

        pad_penalty = (1 - attention_mask).astype(jnp.float32) * _MASK_NEG
        start_logits = start_logits.astype(jnp.float32) + pad_penalty
        end_logits = end_logits.astype(jnp.float32) + pad_penalty

        if packed:
            # per-SEGMENT heads: every original example inside a packed row
            # gets its own span distribution, class logits and regressors.
            # Outputs become [B, S, ...]; downstream (packed loss, packed
            # score_fn) scatters them back to per-chunk results through the
            # segment_mask. Same parameters as the unpacked path (the Dense
            # heads act on the trailing feature dim), so checkpoints are
            # interchangeable between packing settings.
            S = segment_starts.shape[1]
            # [B, S, L]: segment s's logits confined to its own tokens
            seg_eq = (
                segment_ids[:, None, :]
                == (1 + jnp.arange(S, dtype=segment_ids.dtype))[None, :, None]
            )
            seg_penalty = jnp.where(seg_eq, 0.0, jnp.float32(_MASK_NEG))
            start_logits = start_logits[:, None, :] + seg_penalty
            end_logits = end_logits[:, None, :] + seg_penalty
            # pooled_output is already [B, S, H]: the encoder gathered each
            # segment's [CLS] row through its pooler (encoder.py)

        # 5-class answer-type classification on pooled output (model.py:33-34,61)
        cls_hidden = nn.Dropout(cfg.hidden_dropout_prob)(
            pooled_output, deterministic=deterministic
        )
        classifier_logits = _dense(self.quantize, cfg.num_labels,
                                   name="classifier",
                                   dtype=self.dtype)(cls_hidden)

        # normalized-position regressors (model.py:37-41,64-65)
        reg_start = nn.sigmoid(
            _dense(self.quantize, 1, name="reg_start",
                   dtype=self.dtype)(pooled_output)
        )[..., 0]
        reg_end = nn.sigmoid(
            _dense(self.quantize, 1, name="reg_end",
                   dtype=self.dtype)(pooled_output)
        )[..., 0]

        return {
            "start_class": start_logits,
            "end_class": end_logits,
            "start_reg": reg_start.astype(jnp.float32),
            "end_reg": reg_end.astype(jnp.float32),
            "cls": classifier_logits.astype(jnp.float32),
        }

"""Composition root.

Parity target: reference ``modules/init.py`` — loss zoo selection
(``init_loss`` init.py:18-40), model+tokenizer construction with fast-native
vs HF fallback (``init_model`` init.py:51-82), dataset construction with
label/sampler weight computation (``init_datasets`` init.py:148-201), collate
binding (``init_collate_fun`` init.py:204-205).

Optimizer construction (reference ``init_optimizer`` init.py:134-145 +
``_get_optimized_parameters`` init.py:85-131) lives in
:func:`ml_recipe_tpu.train.optim.build_optimizer`, invoked inside the Trainer
— on TPU the optimizer is part of the jitted step, so it must be built where
the step is compiled (it needs ``num_training_steps`` for the schedule).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Optional, Tuple

import jax
import numpy as np

from .data import (
    ChunkDataset,
    DummyDataset,
    RawPreprocessor,
    SplitDataset,
    collate_fun,
)
from .losses import WeightedLoss, build_loss
from .metrics import trace
from .models import MODEL_PRESETS, QAModel, resolve_model_config
from .models.config import DecoderConfig
from .models.hf_convert import load_pretrained_into
from .tokenizer import Tokenizer

logger = logging.getLogger(__name__)


def init_loss(params, train_weights=None) -> WeightedLoss:
    """Loss zoo selection + per-head weights (init.py:18-40)."""
    loss = build_loss(params, train_weights)
    logger.info(f"Used loss function for classification: {params.loss}.")
    return loss


def init_tokenizer(model_params, *, bpe_dropout: Optional[float] = None):
    """First-party fast tokenizer when a vocab file is given; HF fallback
    otherwise (init.py:57-77 semantics, minus the Rust dependency)."""
    # a preset may name the vocabulary file's format itself (a causal
    # trunk's preset does: its rows are ready-made ids over a word vocabulary)
    model_name = getattr(MODEL_PRESETS.get(model_params.model),
                         "tokenizer_family", model_params.model.split("-")[0])

    if model_params.vocab_file is not None and not os.path.exists(model_params.vocab_file):
        raise FileNotFoundError(
            f"vocab_file {model_params.vocab_file!r} does not exist. Generate one "
            f"(ml_recipe_tpu.tokenizer.write_synthetic_bert_vocab) or fix the path."
        )

    if model_params.vocab_file is not None:
        return Tokenizer(
            model_name=model_name,
            vocab_file=model_params.vocab_file,
            merges_file=model_params.merges_file,
            lowercase=model_params.lowercase,
            handle_chinese_chars=model_params.handle_chinese_chars,
            dropout=bpe_dropout,
        )

    logger.warning("No vocab file given; falling back to the slower tokenizer path.")
    try:
        if model_name == "bert":
            from transformers import BertTokenizer

            tokenizer = BertTokenizer.from_pretrained(model_params.model)
        elif model_name == "roberta":
            from transformers import RobertaTokenizer

            tokenizer = RobertaTokenizer.from_pretrained(model_params.model)
        else:
            raise NotImplementedError(model_name)
    except Exception as e:  # offline environments have no HF hub access
        raise RuntimeError(
            f"No vocab_file given and HF tokenizer for {model_params.model!r} "
            f"unavailable ({e}). Pass --vocab_file."
        ) from e

    tokenizer.model_name = model_name
    return tokenizer


def init_model(
    model_params,
    *,
    checkpoint: Optional[str] = None,
    bpe_dropout: Optional[float] = None,
    rng_seed: int = 0,
    mesh=None,
    quantize: str = "off",
) -> Tuple[QAModel, dict, object]:
    """Build (model, params, tokenizer) — reference init.py:51-82.

    Weight priority: explicit ``checkpoint`` (our msgpack format, model part
    only — the reference's strict=False torch.load, init.py:43-48) >
    ``model_params.hf_checkpoint`` (converted HF torch weights) > random init.

    ``quantize='int8'`` (serving/eval only): AFTER the float checkpoint is
    restored, the (model, params) pair is converted through
    ``quant.quantize_model`` — post-training per-channel int8, no
    retraining, any existing checkpoint — and the per-layer error summary
    is logged. The checkpoint format itself never changes.
    """
    with trace.span("init_model", cat="setup"):
        import jax.numpy as jnp

        tokenizer = init_tokenizer(model_params, bpe_dropout=bpe_dropout)

        cfg = resolve_model_config(model_params, num_labels=len(RawPreprocessor.labels2id))
        dtype = jnp.bfloat16 if getattr(model_params, "compute_dtype", "bfloat16") == "bfloat16" else jnp.float32
        attention_impl = getattr(model_params, "flash_attention", "auto") or "auto"
        if attention_impl == "auto" and mesh is not None:
            from .parallel.sharding import SEQ_AXIS

            if SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1:
                # a seq axis in the mesh IS the long-context request: route
                # attention through the ring dispatcher, which consumes each
                # visiting K/V shard via the composed streaming inner when a
                # legal geometry exists at the local length
                attention_impl = "ring"
                logger.info(
                    "Mesh has seq:%d — attention_impl auto-selected 'ring' "
                    "(composed streaming-ring for long documents).",
                    mesh.shape[SEQ_AXIS],
                )
        if isinstance(cfg, DecoderConfig):
            from .models.mla_moe import unsupported

            unsupported(cfg, mesh=mesh, quantize=quantize,
                        attention_impl=attention_impl)
            if getattr(model_params, "hf_checkpoint", None):
                raise NotImplementedError(
                    f"loading a published checkpoint into the {cfg.model_type} "
                    f"trunk (models/hf_convert.py converts BERT/RoBERTa only)")
        model = QAModel(
            cfg,
            dtype=dtype,
            attention_impl=attention_impl,
            remat=getattr(model_params, "remat", False),
            mesh=mesh,  # required by attention_impl='ring' (sequence parallelism)
            ln_impl=getattr(model_params, "ln_impl", "xla") or "xla",
        )

        example = np.zeros((1, 8), dtype=np.int32)
        # Init through an XLA-attention twin: param structure is identical across
        # attention impls, and ring's shard_map would reject the tiny example
        # shape (batch/seq not divisible by the mesh axes).
        init_module = (
            dataclasses.replace(model, attention_impl="xla", mesh=None)
            if model.attention_impl == "ring"
            else model
        )
        params = init_module.init(jax.random.key(rng_seed), example)["params"]

        hf_checkpoint = getattr(model_params, "hf_checkpoint", None)
        if hf_checkpoint:
            params = load_pretrained_into(params, hf_checkpoint, cfg.num_layers)
            logger.info(f"Encoder weights converted from HF checkpoint {hf_checkpoint}.")

        if checkpoint is not None:
            from .train.checkpoint import load_state_dict

            params, _, _, loaded_step = load_state_dict(checkpoint, params=params)
            if loaded_step is not None:
                logger.info(f"Model checkpoint was restored from {checkpoint}.")

        if quantize not in (None, "off"):
            from .quant import quantize_model

            model, params, report = quantize_model(model, params, quantize)
            logger.info(
                "Post-training quantization (%s): %d kernels converted, "
                "params %.1f -> %.1f MB (kernels %.1f -> %.1f MB), worst "
                "per-layer relative RMS error %.4f.",
                quantize, report["n_quantized"],
                report["orig_bytes"] / 1e6, report["quant_bytes"] / 1e6,
                report["orig_kernel_bytes"] / 1e6,
                report["quant_kernel_bytes"] / 1e6,
                report["max_rel_rms_err"],
            )

        return model, params, tokenizer


def init_datasets(params, *, tokenizer=None, clear: bool = False, rng=None):
    """Datasets + label/sampler weights (init.py:148-201).

    TPU delta: the test dataset is built on EVERY process (eval runs SPMD;
    the reference gated it to rank 0, init.py:195-200).
    """
    with trace.span("init_datasets", cat="setup"):
        weights = {"label_weights": None, "sampler_weights": None}

        if getattr(params, "dummy_dataset", False):
            logger.warning("Dummy dataset is used to train model.")
            common = dict(
                data_dir=None,
                tokenizer=tokenizer,
                indexes=None,
                max_seq_len=params.max_seq_len,
                max_question_len=params.max_question_len,
                rng=rng,
            )
            return DummyDataset(**common), DummyDataset(dataset_len=1024, **common), weights

        preprocessor = RawPreprocessor(
            raw_json=params.data_path, out_dir=params.processed_data_path, clear=clear
        )
        labels_counter, labels, (train_indexes, train_labels, test_indexes, test_labels) = (
            preprocessor()
        )

        if getattr(params, "train_label_weights", False):
            label_weights = np.asarray(
                [1 / labels_counter[k] for k in sorted(labels_counter.keys())]
            )
            label_weights = label_weights / np.sum(label_weights)
            logger.info(
                "Label weights: "
                + ", ".join(
                    f"{RawPreprocessor.id2labels[k]} ({k}) - {v:.4f}"
                    for k, v in enumerate(label_weights)
                )
                + "."
            )
            weights["label_weights"] = label_weights

        if getattr(params, "train_sampler_weights", False):
            sampler_weights = np.asarray([1 / labels_counter[label] for label in train_labels])
            weights["sampler_weights"] = sampler_weights / np.sum(sampler_weights)

        common = dict(
            tokenizer=tokenizer,
            max_seq_len=params.max_seq_len,
            max_question_len=params.max_question_len,
            doc_stride=params.doc_stride,
            split_by_sentence=params.split_by_sentence,
            truncate=params.truncate,
            rng=rng,
        )
        train_dataset = SplitDataset(params.processed_data_path, indexes=train_indexes, **common)
        test_dataset = SplitDataset(
            params.processed_data_path, indexes=test_indexes, test=True, **common
        )

        return train_dataset, test_dataset, weights


def init_validation_dataset(params, *, tokenizer=None, clear: bool = False, rng=None):
    """Held-out split as a ChunkDataset (reference validate.py:15-26)."""
    preprocessor = RawPreprocessor(
        raw_json=params.data_path, out_dir=params.processed_data_path, clear=clear
    )
    _, _, (_, _, val_indexes, val_labels) = preprocessor()

    return ChunkDataset(
        params.processed_data_path,
        tokenizer,
        val_indexes,
        test=False,
        split_by_sentence=True,
        truncate=True,
        rng=rng,
    )


def init_collate_fun(tokenizer, *, max_seq_len: Optional[int] = None, return_items: bool = False):
    """Bind tokenizer + static shape (init.py:204-205; fixed-shape TPU delta)."""
    return functools.partial(
        collate_fun, tokenizer=tokenizer, max_seq_len=max_seq_len, return_items=return_items
    )

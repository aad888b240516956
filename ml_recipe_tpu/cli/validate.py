"""Validation / prediction entry point.

Parity target: reference ``modules/validate.py`` — load checkpoint, build a
``ChunkDataset`` over the held-out split (validate.py:15-26), run the
``Predictor`` over all chunks (validate.py:29-54).

The reference swapped its fast Rust tokenizer for the slow HF one here
because the Rust object could not cross ``mp.Pool`` pickling
(validate.py:37-39 todo). Our first-party tokenizer streams through the
thread-pool ``ListDataloader`` directly — no swap needed.

Usage::

    python -m ml_recipe_tpu.cli.validate -c config/validate.cfg
"""

from __future__ import annotations

import os

from ..compose import init_collate_fun, init_model, init_validation_dataset
from ..config.parser import get_model_parser, get_params, get_predictor_parser
from ..data.bucketing import parse_length_buckets
from ..infer import Predictor
from ..parallel import ParallelPlan
from ..utils.logging import get_logger, show_params


def main(params, model_params):
    show_params(model_params, "model")
    show_params(params, "predictor")

    # --quantize int8: offline eval of the int8 serving path — the same
    # conversion the serving engine performs at startup, so span-level
    # accuracy of a quantized deployment can be measured before it ships
    model, model_state, tokenizer = init_model(
        model_params, checkpoint=params.checkpoint,
        quantize=getattr(params, "quantize", "off"),
    )

    val_dataset = init_validation_dataset(params, tokenizer=tokenizer, clear=False)

    collate_fun = init_collate_fun(
        tokenizer, max_seq_len=params.max_seq_len, return_items=True
    )
    predictor = Predictor(
        model,
        model_state,
        # one declarative plan from --mesh; the predictor derives its
        # batch placement from it
        mesh=ParallelPlan.from_spec(getattr(params, "mesh", None)).mesh,
        collate_fun=collate_fun,
        batch_size=params.batch_size,
        n_jobs=params.n_jobs,
        buffer_size=params.buffer_size,
        limit=params.limit,
        fetch_every=params.fetch_every,
        length_buckets=parse_length_buckets(
            getattr(params, "length_buckets", None), params.max_seq_len
        ),
        sequence_packing=getattr(params, "sequence_packing", False),
        pack_max_segments=getattr(params, "pack_max_segments", 8),
        pack_splitting=getattr(params, "pack_splitting", "off"),
        pack_min_fragment=getattr(params, "pack_min_fragment", 32),
    )

    predictor(val_dataset)

    return predictor


def cli() -> None:
    from ..utils.platform import configure_compile_cache

    configure_compile_cache()
    _, (params, model_params) = get_params((get_predictor_parser, get_model_parser))
    get_logger(logger_name="validate")

    params.n_jobs = max(1, min(params.n_jobs, (os.cpu_count() or 2) // 2))

    main(params, model_params)


if __name__ == "__main__":
    cli()

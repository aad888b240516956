"""Offline metric evaluation entry point.

Parity target: reference ``modules/train_metrics.py`` — re-run the Trainer's
test loop with MAP/Accuracy callbacks on BOTH the train and test splits from
a saved checkpoint (train_metrics.py:13-55).

Usage::

    python -m ml_recipe_tpu.cli.train_metrics -c config/validate.cfg
"""

from __future__ import annotations

import logging
import os

from ..compose import init_collate_fun, init_datasets, init_loss, init_model
from ..config.parser import (
    get_model_parser,
    get_params,
    get_predictor_parser,
    get_trainer_parser,
)
from ..data import RawPreprocessor
from ..parallel import build_mesh
from ..train import AccuracyCallback, MAPCallback, Trainer
from ..utils.logging import get_logger, show_params

logger = logging.getLogger(__name__)


def run_test(params):
    """Test-only Trainer (train_metrics.py:13-34)."""
    trainer = Trainer(
        model=params.model,
        params=params.model_state,
        loss=params.loss,
        collate_fun=params.collate_fun,
        test_dataset=params.dataset,
        mesh=params.mesh_obj,
        test_batch_size=params.batch_size,
        n_jobs=params.n_jobs,
        debug=getattr(params, "debug", False),
    )

    callbacks = [
        MAPCallback(list(RawPreprocessor.labels2id.keys())),
        AccuracyCallback(),
    ]

    return trainer.test(-1, callbacks=callbacks)


def main(params, model_params) -> None:
    show_params(model_params, "model")
    show_params(params, "test")

    params.model, params.model_state, params.tokenizer = init_model(
        model_params, checkpoint=params.checkpoint
    )
    params.mesh_obj = build_mesh(getattr(params, "mesh", None))

    train_dataset, test_dataset, weights = init_datasets(
        params, tokenizer=params.tokenizer, clear=False
    )
    params.loss = init_loss(params, weights)
    params.collate_fun = init_collate_fun(params.tokenizer, max_seq_len=params.max_seq_len)

    logger.info("Train dataset validation..")
    params.dataset = train_dataset
    run_test(params)

    logger.info("Test dataset validation..")
    params.dataset = test_dataset
    run_test(params)


def cli() -> None:
    from ..utils.platform import configure_compile_cache

    configure_compile_cache()
    # The reference parsed with the predictor parser only (train_metrics.py:59)
    # yet init_loss/init_datasets read trainer-parser flags (loss, w_*,
    # dummy_dataset, ...) — a latent crash. Route all three parsers and fill
    # loss/dataset knobs from the trainer namespace.
    _, (params, trainer_ns, model_params) = get_params(
        (get_predictor_parser, get_trainer_parser, get_model_parser)
    )
    for key, value in vars(trainer_ns).items():
        if not hasattr(params, key):
            setattr(params, key, value)

    params.n_jobs = max(1, min(params.n_jobs, (os.cpu_count() or 2) // 2))

    get_logger(logger_name="train_metrics")

    main(params, model_params)


if __name__ == "__main__":
    cli()

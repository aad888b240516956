"""The train runner with the configuration's own comparison: a configuration
whose block is not the BERT encoder names, under ``comparison``, the module of
``harness/`` whose ``compare`` decides ``correct`` (same arguments and report
as ``runners.train.check_against_reference``, which binds the BERT reference
by import). Everything else is ``runners.train``."""

from __future__ import annotations

import importlib

from . import train


def run(cell, **how) -> int:
    compare = importlib.import_module(
        f"perfbench.harness.{cell.config['comparison']}").compare
    bound = train.check_against_reference
    train.check_against_reference = compare
    try:
        return train.run(cell, **how)
    finally:
        train.check_against_reference = bound

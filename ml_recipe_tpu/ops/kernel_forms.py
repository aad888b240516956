"""Which form of a kernel family this process has traced, and why a Mosaic
kernel was refused.

A family whose operator runs either as the repo's Mosaic kernels or as an
XLA form counts each call it traces under its form: ``count(family, form,
n)``. ``traced()`` is the record; ``traced(since=before)`` what one trace
added after the snapshot ``before``, which is what that step program holds
(the pre-flight's ``kernel_calls``). The families and their forms:

- ``grouped_matmul`` (``ops/grouped_matmul.py``): ``kernel`` (a call counts
  its backward's two), ``ragged_dot`` (its backward is JAX's own);
- ``token_rows`` (``ops/token_rows.py``): ``kernel``, ``xla``;
- ``attn_tiles`` (``ops/flash_causal.py``): the 128 x 128 sub-tiles the
  causal and window kernels ``computed`` under their strip cuts, and those
  their grids ``walked``.

What the compiled program holds is read from its instruction names by
``metrics.trace.kernel_calls``.
"""

from __future__ import annotations

import functools
import logging
from collections import Counter, defaultdict
from typing import Dict, Optional

_counts: Dict[str, Counter] = defaultdict(Counter)


def count(family: str, form: str, n: int = 1) -> None:
    _counts[family][form] += n


def traced(since: Optional[dict] = None) -> Dict[str, Dict[str, int]]:
    """``{family: {form: n}}`` traced in this process so far; with
    ``since`` (an earlier ``traced()``), what was traced after it: the
    families and forms with any count."""
    now = {family: dict(forms) for family, forms in _counts.items()}
    if since is None:
        return now
    added = {family: {form: n - since.get(family, {}).get(form, 0)
                      for form, n in forms.items()}
             for family, forms in now.items()}
    return {family: {form: n for form, n in forms.items() if n}
            for family, forms in added.items() if any(forms.values())}


@functools.lru_cache(maxsize=None)
def log_refusal(logger: logging.Logger, message: str) -> None:
    """``logger`` warns ``message`` (a family's refusal, its reason in it)
    once: every layer of a trunk traces the same shapes."""
    logger.warning(message)

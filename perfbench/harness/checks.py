"""The comparisons that decide ``correct``. Tolerances, with their reasons:

``logit_tolerances`` - the system computes in bfloat16 (8 significant bits:
values between 1 and 2 are 2^-7 apart) with float32 accumulation, the
reference in float32 at highest precision. Every layer writes the
unit-variance post-LN hidden state back in bf16, so the state a head reads
carries independent rounding errors of about one spacing an element that grow
as the square root of the depth; a head column ``w`` turns them into
``|w|_2`` times that. The error allowed on a logit is therefore
``2 * 2^-7 * sqrt(layers) * |w|_2`` (0.054 |w| for twelve layers, 0.077 |w|
for twenty-four), and ``2 * 2^-7`` on the sigmoid regressors. Measured on
the chip for bert-base: 0.024-0.026, the same on every seed whatever the
size of the logits (PR 22), so the margin is a factor of two. int8 weights
and activations (a per-element error four to eight times bf16's) or a
dropped term (a bias, the token-type embedding, the 1/sqrt(d) scale; tenths
of a logit and more) land outside it.

``LOSS_RTOL`` / ``MESH_RTOL`` - a loss is a mean over rows of log-softmax
values of those logits: 1e-2 relative, the order of f32 reductions over bf16
products being the only other difference (``chip_smoke.py --chips 4``).
"""

from __future__ import annotations

import numpy as np

LOSS_RTOL = 1e-2
MESH_RTOL = 1e-2


def seeded_rows(seed: int, vocab: int, seq_len: int, lengths) -> tuple:
    """Rows of ``[CLS] q.. [SEP] d.. [SEP]`` with ragged true lengths, the
    collate's three planes and its five labels."""
    rng = np.random.default_rng([seed, 0xC0 + len(lengths)])
    n = len(lengths)
    ids = np.zeros((n, seq_len), np.int32)
    types = np.zeros((n, seq_len), np.int32)
    for i, k in enumerate(lengths):
        q = min(12, max(1, k // 4))
        ids[i, :k] = rng.integers(10, vocab, size=k)
        ids[i, 0], ids[i, q + 1], ids[i, k - 1] = 2, 3, 3
        types[i, q + 2:k] = 1
    mask = (np.arange(seq_len)[None, :] < np.asarray(lengths)[:, None])
    starts = np.asarray([int(rng.integers(1, k - 1)) for k in lengths])
    ends = np.asarray([int(rng.integers(s, k - 1))
                       for s, k in zip(starts, lengths)])
    labels = {
        "start_class": starts.astype(np.int32),
        "end_class": ends.astype(np.int32),
        "start_reg": (starts / seq_len).astype(np.float32),
        "end_reg": (ends / seq_len).astype(np.float32),
        "cls": rng.integers(0, 5, size=n).astype(np.int32),
    }
    if n > 1:
        labels["start_class"][1] = labels["end_class"][1] = -1   # 'unknown'
    inputs = {"input_ids": ids, "attention_mask": mask.astype(np.int32),
              "token_type_ids": types}
    return inputs, labels


def logit_tolerances(params: dict, n_layers: int) -> dict:
    """The largest absolute error allowed, head by head (see above)."""
    ulp2 = 2 * 2.0 ** -7

    def col_norm(name):
        w = np.asarray(params[name]["kernel"], np.float32)
        return float(np.sqrt((w ** 2).sum(0)).max())

    deep = ulp2 * float(np.sqrt(n_layers))
    span = deep * col_norm("position_outputs")
    return {"start_class": span, "end_class": span,
            "cls": deep * col_norm("classifier"),
            "start_reg": ulp2, "end_reg": ulp2}


def absolute_errors(got: dict, want: dict, mask) -> dict:
    """Largest absolute error, head by head, on the positions the mask
    keeps."""
    valid = np.asarray(mask, bool)
    out = {}
    for key in ("start_class", "end_class", "cls", "start_reg", "end_reg"):
        a = np.asarray(got[key], np.float32)
        b = np.asarray(want[key], np.float32)
        if a.ndim == 2 and a.shape == valid.shape:
            a, b = a[valid], b[valid]
        out[key] = (float(np.abs(a - b).max()) if np.isfinite(a).all()
                    else float("inf"))
    return out


def within(errors: dict, tolerances: dict) -> bool:
    return all(errors[k] <= tolerances[k] for k in tolerances)


def close(a: float, b: float, rtol: float) -> bool:
    return bool(np.isfinite(a) and np.isfinite(b)
                and abs(a - b) <= rtol * max(abs(a), abs(b)))

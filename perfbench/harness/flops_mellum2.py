"""Operations and bytes the ``mellum`` trunk needs, from shapes and from what
the routing counter saw. Needed work only: a causal row counts its ``L(L+1)/2``
(query, key) pairs, a sliding-window row the pairs its band permits (``W L -
W (W - 1) / 2`` for ``L >= W``: 7,864,832 at 8,192 under 1,024, not the
12,582,912 the kernels' blocks of 512 compute), the routed experts count the
assignments this process holds, and nothing recomputed counts. A
multiply-add is 2 FLOPs; backward of a matmul costs twice its forward, so
train = 3 x forward. The projections', the cores' bytes and the grouped
matmuls' counts are ``flops_lfm2``'s: they read the same keys.
"""

from __future__ import annotations

from .flops_lfm2 import (attention_projection_flops,  # noqa: F401
                         grouped_matmul_bytes, grouped_matmul_flops, head_dim,
                         held_per_token_expected, swiglu_flops)
from .flops_lfm2 import causal_core_bytes as core_bytes  # noqa: F401

KINDS = ("sliding_attention", "full_attention")


def permitted_pairs(cfg: dict, kind: str, seq_len: int) -> float:
    """(query, key) pairs one row of ``seq_len`` tokens has under the mask of
    a layer of ``kind``."""
    window = cfg["sliding_window"] if kind == "sliding_attention" else seq_len
    window = min(window, seq_len)
    return window * seq_len - window * (window - 1) / 2.0


def core_flops(cfg: dict, kind: str, rows: float, seq_len: int,
               *, train: bool) -> float:
    """One layer's attention core for ``rows`` rows: QK^T and PV over the
    permitted pairs of every QUERY head (a shared key/value head saves bytes,
    not products); backward needs dV, dP, dQ, dK: twice the forward."""
    fwd = 2.0 * rows * cfg["num_attention_heads"] * permitted_pairs(
        cfg, kind, seq_len) * 2 * head_dim(cfg)
    return fwd * (3 if train else 1)


def matmul_flops_per_token(cfg: dict, seq_len: int, *, train: bool,
                           held_per_token=None) -> float:
    """Matmul FLOPs a trained (or inferred) token needs through the whole
    trunk as it is held here. ``held_per_token``: assignments to held experts
    a token and expert layer, from the counter (default: the expectation).
    The embedding gather, the norms, the rotation and the QA heads are under
    1% and left out."""
    C = cfg["hidden_size"]
    kinds = cfg["layer_types"]
    if held_per_token is None:
        held_per_token = held_per_token_expected(cfg)
    cores = sum(core_flops(cfg, kind, 1.0, seq_len, train=False)
                for kind in kinds) / seq_len
    moe = (2.0 * C * cfg["experts_held"]["of"]        # the router
           + held_per_token * swiglu_flops(C, cfg["moe_intermediate_size"]))
    fwd = len(kinds) * (attention_projection_flops(cfg) + moe) + cores
    return fwd * (3 if train else 1)

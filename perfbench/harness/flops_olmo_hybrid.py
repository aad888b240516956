"""Operations and bytes the ``olmo_hybrid`` trunk needs, from shapes. Needed
work only: causal attention counts the ``L(L+1)/2`` (query, key) pairs a row
has, the delta rule counts the products of its recurrence and each tensor it
must read or write once, and nothing recomputed counts (``remat``'s second
forward is not needed work). A multiply-add is 2 FLOPs; backward of a matmul
costs twice its forward, so train = 3 x forward. The attention core's counts
are ``flops_lfm2``'s: they read the same keys.
"""

from __future__ import annotations

from .flops_joyai import swiglu_flops
from .flops_lfm2 import (attention_projection_flops, causal_core_bytes,  # noqa: F401
                         causal_core_flops, head_dim)


def linear_projection_flops(cfg: dict) -> float:
    """One token through a linear-attention layer's projections (q, k, v,
    the gate, the decay's and the write strength's, the output), forward."""
    C, H = cfg["hidden_size"], cfg["linear_num_value_heads"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = H * cfg["linear_value_head_dim"]
    return 2.0 * (C * (2 * keys + 2 * values + 2 * H) + values * C)


def gated_delta_flops(cfg: dict, tokens: float, *, train: bool) -> float:
    """One layer's recurrence for ``tokens`` tokens, whatever implements it:
    four products of ``d_k x d_v`` a token and head forward (the state's
    decay, the read ``S k``, the write ``u k^T``, the output ``S q``); a
    chunked form's solve and its products within a chunk are its own cost,
    not the rule's."""
    fwd = 4 * 2.0 * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return float(tokens * fwd * (3 if train else 1))


def gated_delta_bytes(cfg: dict, tokens: float, *, train: bool,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads ``q``, ``k``, ``v``
    (compute dtype) and ``g``, ``beta`` (f32) and writes ``o``; backward reads
    them and ``o``'s cotangent and writes the five gradients. The state lives
    on the chip."""
    H = cfg["linear_num_value_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    read = H * ((2 * d_k + d_v) * itemsize + 2 * 4)
    wrote = H * d_v * itemsize
    fwd = read + wrote
    bwd = read + wrote + read
    return float(tokens * (fwd + (bwd if train else 0)))


def matmul_flops_per_token(cfg: dict, seq_len: int, *, train: bool) -> float:
    """Matmul FLOPs a trained (or inferred) token needs through the whole
    trunk as it is held here: every layer's dense SwiGLU, the linear layers'
    projections and recurrence, the attention layers' projections and causal
    pairs. The embedding gather, the norms, the convolutions' taps, the
    gating and the QA heads are under 1% and left out."""
    kinds = cfg["layer_types"]
    attention = attention_projection_flops(cfg) + causal_core_flops(
        cfg, 1.0, seq_len, train=False) / seq_len
    linear = linear_projection_flops(cfg) + gated_delta_flops(
        cfg, 1.0, train=False)
    fwd = (len(kinds) * swiglu_flops(cfg["hidden_size"],
                                     cfg["intermediate_size"])
           + kinds.count("linear_attention") * linear
           + kinds.count("full_attention") * attention)
    return fwd * (3 if train else 1)

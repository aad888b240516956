"""Operations and bytes the ``lfm2_moe`` trunk needs, from shapes and from
what the routing counter saw. Needed work only: causal attention counts the
``L(L+1)/2`` (query, key) pairs a row has, the routed experts count the
assignments this process holds, the short convolution counts each tensor it
must read or write once, and nothing recomputed counts. A multiply-add is 2
FLOPs; backward of a matmul costs twice its forward, so train = 3 x forward.
The grouped matmuls' counts are ``flops_joyai``'s: they read the same keys.
"""

from __future__ import annotations

from .flops_joyai import (grouped_matmul_bytes, grouped_matmul_flops,  # noqa: F401
                          held_per_token_expected, swiglu_flops)


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def conv_projection_flops(cfg: dict) -> float:
    """One token through a conv layer's two projections (hidden -> 3 hidden,
    hidden -> hidden), forward."""
    C = cfg["hidden_size"]
    return 2.0 * (C * 3 * C + C * C)


def attention_projection_flops(cfg: dict) -> float:
    """One token through an attention layer's q, k, v and output projections,
    forward: k and v have the key/value heads' width."""
    C, d = cfg["hidden_size"], head_dim(cfg)
    H, H_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2.0 * (C * H * d + 2 * C * H_kv * d + H * d * C)


def causal_core_flops(cfg: dict, rows: float, seq_len: int,
                      *, train: bool) -> float:
    """One layer's attention core for ``rows`` rows: QK^T and PV over the
    causal pairs of every QUERY head (a shared key/value head saves bytes,
    not products); backward needs dV, dP, dQ, dK: twice the forward."""
    pairs = seq_len * (seq_len + 1) / 2.0
    fwd = 2.0 * rows * cfg["num_attention_heads"] * pairs * 2 * head_dim(cfg)
    return fwd * (3 if train else 1)


def causal_core_bytes(cfg: dict, rows: float, seq_len: int, *, train: bool,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's core: forward reads q and writes the
    context a query head, reads k and v a key/value head; backward reads q,
    the context and its cotangent and writes dq a query head, reads k and v
    and writes dk and dv a key/value head (k/v bytes once a key/value head,
    however the backward sums a group)."""
    d = head_dim(cfg)
    H, H_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    fwd = (2 * H + 2 * H_kv) * d * itemsize
    bwd = (4 * H + 4 * H_kv) * d * itemsize
    return float(rows * seq_len * (fwd + (bwd if train else 0)))


def short_conv_bytes(cfg: dict, tokens: float, *, train: bool,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's gating and taps for ``tokens``
    tokens: forward reads the projection's three thirds and writes one;
    backward reads them and the result's cotangent and writes the three
    thirds' gradient (the taps and their gradient are a few KB)."""
    C = cfg["hidden_size"]
    fwd = 4 * C * itemsize
    bwd = 7 * C * itemsize
    return float(tokens * (fwd + (bwd if train else 0)))


def short_conv_flops(cfg: dict, tokens: float, *, train: bool) -> float:
    """Elementwise operations of the same: ``Bg * x``, ``K`` products and
    ``K - 1`` sums, ``Cg *`` forward; backward the cotangent's gate, the
    transposed taps, three gate products, the taps' own gradient (a product
    and a sum each) and nothing recomputed."""
    C, K = cfg["hidden_size"], cfg["conv_L_cache"]
    fwd = C * (2 * K + 1)
    bwd = C * (1 + (2 * K - 1) + 3 + 2 * K)
    return float(tokens * (fwd + (bwd if train else 0)))


def matmul_flops_per_token(cfg: dict, seq_len: int, *, train: bool,
                           held_per_token=None) -> float:
    """Matmul FLOPs a trained (or inferred) token needs through the whole
    trunk as it is held here. ``held_per_token``: assignments to held experts
    a token and expert layer, from the counter (default: the expectation).
    The embedding gather, the norms, RoPE, the convolution's gating and the
    QA heads are under 1% and left out."""
    C = cfg["hidden_size"]
    kinds = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    if held_per_token is None:
        held_per_token = held_per_token_expected(cfg)
    attention = attention_projection_flops(cfg) + causal_core_flops(
        cfg, 1.0, seq_len, train=False) / seq_len
    moe = (2.0 * C * cfg["experts_held"]["of"]        # the router
           + held_per_token * swiglu_flops(C, cfg["moe_intermediate_size"]))
    fwd = (kinds.count("conv") * conv_projection_flops(cfg)
           + kinds.count("full_attention") * attention
           + dense * swiglu_flops(C, cfg["intermediate_size"])
           + (len(kinds) - dense) * moe)
    return fwd * (3 if train else 1)

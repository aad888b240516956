"""Operations and bytes the ``joyai_llm_flash`` trunk needs, from shapes and
from what the routing counter saw. Needed work only: causal attention counts
the ``L(L+1)/2`` (query, key) pairs a row has, the routed experts count the
assignments this process holds (not the 8 a token makes, not a buffer's
rows), and nothing recomputed counts. A multiply-add is 2 FLOPs; backward of
a matmul costs twice its forward, so train = 3 x forward.
"""

from __future__ import annotations


def mla_projection_flops(cfg: dict) -> float:
    """One token through one layer's five MLA projections, forward."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * (
        C * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * qk
        + C * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                     + cfg["v_head_dim"])
        + H * cfg["v_head_dim"] * C)


def causal_core_flops(cfg: dict, rows: float, seq_len: int,
                      *, train: bool) -> float:
    """One layer's attention core for ``rows`` rows: QK^T (d_qk) and PV (d_v)
    over the causal pairs; backward needs dV, dP (d_v each) and dQ, dK (d_qk
    each): twice the forward. The recompute of QK^T in a flash backward is
    not the algorithm's and is not counted."""
    pairs = seq_len * (seq_len + 1) / 2.0
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    fwd = 2.0 * rows * cfg["num_attention_heads"] * pairs * (
        qk + cfg["v_head_dim"])
    return fwd * (3 if train else 1)


def causal_core_bytes(cfg: dict, rows: float, seq_len: int, *, train: bool,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's core: forward reads q, k (d_qk), v
    and writes the context (d_v); backward reads q, k, v, the context and its
    cotangent and writes dq, dk, dv."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    per_token = cfg["num_attention_heads"] * itemsize
    fwd = (2 * qk + 2 * dv) * per_token
    bwd = (2 * qk + 3 * dv + 2 * qk + dv) * per_token
    return float(rows * seq_len * (fwd + (bwd if train else 0)))


def swiglu_flops(hidden: int, width: int) -> float:
    """One token through gate, up and down, forward."""
    return 2.0 * 3 * hidden * width


def held_per_token_expected(cfg: dict) -> float:
    """Assignments a token makes to the experts held here, in expectation
    under uniform routing: ``top-k x held / routed``."""
    return (cfg["num_experts_per_tok"] * cfg["experts_held"]["count"]
            / cfg["experts_held"]["of"])


def matmul_flops_per_token(cfg: dict, seq_len: int, *, train: bool,
                           held_per_token=None) -> float:
    """Matmul FLOPs a trained (or inferred) token needs through the whole
    trunk as it is held here. ``held_per_token``: assignments to held experts
    a token and expert layer, from the counter (default: the expectation).
    The embedding gather, the norms, RoPE and the QA heads are under 1% and
    left out."""
    C = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    if held_per_token is None:
        held_per_token = held_per_token_expected(cfg)
    attention = mla_projection_flops(cfg) + causal_core_flops(
        cfg, 1.0, seq_len, train=False) / seq_len
    expert = swiglu_flops(C, cfg["moe_intermediate_size"])
    moe = (cfg["n_shared_experts"] * expert
           + 2.0 * C * cfg["experts_held"]["of"]        # the router
           + held_per_token * expert)
    fwd = (layers * attention
           + dense * swiglu_flops(C, cfg["intermediate_size"])
           + (layers - dense) * moe)
    return fwd * (3 if train else 1)


def grouped_matmul_flops(cfg: dict, assignments: float, *,
                         train: bool) -> float:
    """The routed experts' grouped matmuls for ``assignments`` rows."""
    fwd = assignments * swiglu_flops(
        cfg["hidden_size"], cfg["moe_intermediate_size"])
    return fwd * (3 if train else 1)


def grouped_matmul_bytes(cfg: dict, assignments: float, calls: float, *,
                         train: bool, itemsize: int = 2) -> float:
    """Least HBM traffic of the grouped matmuls: each of ``calls`` (one an
    expert layer and micro-batch) reads the held experts' weights once and,
    per row, reads the token, writes and reads the two hidden halves and
    writes the output; backward reads the weights again, writes their
    gradients in the compute dtype and moves each row's tensors twice."""
    C, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["experts_held"]["count"] * 3 * C * F * itemsize
    row = (2 * C + 4 * F) * itemsize
    fwd = calls * weights + assignments * row
    bwd = calls * 2 * weights + assignments * 2 * row
    return float(fwd + (bwd if train else 0))

"""Operations and bytes the algorithm needs, from shapes. Kept with the
benchmark so that no PR that claims a gain can change the yardstick.

``matmul_flops_per_token`` is a copy of ``bench.py:
_matmul_gflops_per_example`` per token (multiply-add = 2 FLOPs): the
encoder's dense matmuls and the attention score/context dots; embeddings,
pooler and QA heads are under 1% and left out. Backward of a matmul costs
twice its forward (dX and dW), so train = 3 x forward; recomputed operations
never count.
"""

from __future__ import annotations


def matmul_flops_per_token(cfg: dict, seq_len: int, *, train: bool) -> float:
    C = cfg["hidden_size"]
    F = cfg["intermediate_size"]
    fwd = cfg["num_hidden_layers"] * (
        2 * 4 * C * C            # q/k/v/o projections
        + 2 * 2 * C * F          # FFN in/out
        + 4 * seq_len * C        # QK^T and PV, summed over heads
    )
    return float(fwd * 3 if train else fwd)


def attention_flops(batch: int, seq_len: int, heads: int, head_dim: int,
                    *, train: bool) -> float:
    """One layer's attention core for ``batch`` rows. Forward: QK^T and PV,
    2*L*L*D multiply-adds each per head. Backward needs dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q: four more (the recompute of QK^T
    inside a flash backward is not needed by the algorithm and not counted).
    """
    dot = 2.0 * batch * heads * seq_len * seq_len * head_dim
    return dot * (6 if train else 2)


def attention_bytes(batch: int, seq_len: int, heads: int, head_dim: int,
                    *, train: bool, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's attention core: forward reads q, k, v
    and writes the context; backward reads q, k, v, context, d(context) and
    writes dq, dk, dv (the [L, L] scores never need to touch HBM)."""
    tensor = float(batch * seq_len * heads * head_dim * itemsize)
    return tensor * (4 + 8 if train else 4)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound) for one chip of ``peaks``."""
    t_flops = flops / (peaks["bf16_tflops"] * 1e12)
    t_bytes = nbytes / (peaks["hbm_gb_per_s"] * 1e9)
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")

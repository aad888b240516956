"""Causal flash attention under a sliding window: query ``i`` reads the keys
``j`` with ``0 <= i - j < window`` (itself and the ``window - 1`` before it).

The causal family (``ops/flash_causal.py``) walks the ``n(n+1)/2`` (q block,
k block) pairs a causal mask leaves; a window cuts the tables BELOW the
diagonal too. With ``reach = ceil((window - 1) / blk)`` a q block ``a`` keeps
the k blocks ``max(0, a - reach) <= b <= a``, whatever ``L`` is, so a window
layer's grid grows with ``L`` and not ``L^2``. A pair's distance ``d = a - b``
says which mask its tile needs: the diagonal (``d == 0``) the triangle
``j <= i``; a far tile (``d >= edge``, the first distance whose tile holds an
``i - j >= window``) the band's other side ``i - j < band`` with ``band =
window - d * blk``, one kind of tile a distance so that ``band`` is static;
the tiles between neither.

The diagonal and each far tile compute only the row strips and column ranges
their masks can reach (``flash_causal.strip_cuts``, height ``s`` from
``strip_height``): strip ``r`` of the diagonal reads the columns ``[0, (r +
1) s)``; strip ``r`` of a far tile those from its first row's ``i - window +
1``, in 128-lane steps, to the block's end (``[r s, blk)`` at ``band`` 0).
At a window of 1,024 and a block edge of 512 a q block walks three tiles,
the diagonal, one whole block and the far edge at ``band`` 0; at ``s`` 128
the two edges compute 10 of their 16 sub-tiles of 128 x 128 each, so a q
block computes 1,280 keys' worth of the 1,536 it walks, for 1,024 needed
(``tile_area``: 33.75 tiles of 45 at L 8,192). An edge of 256 would walk
five tiles for 1,280 keys, each a quarter of the work of a 512 tile under
the same grid-step cost, so the edge stays ``pick_block``'s, from the shapes
alone.

The kernel bodies, block specs, call builders, the fused backward's VMEM
rule, the layout ``[B, H, L, D]`` and grouped-query heads through the index
maps (the backward writes dk and dv a QUERY head and XLA sums each group in
f32 after the call) are the causal family's: this family is a
``flash_causal.Walk`` of its own. The calls carry names of their own
(``flash_window_fwd``, ``flash_window_bwd``, ``_bwd_dq``, ``_bwd_dkv``): the
trace readers tell window calls from full ones by them.

A far tile's upper rows see none of its keys; their running maximum stays at
the mask's value until a nearer tile brings a permitted key (the diagonal
always does: key ``i`` for query ``i``), whose rescaling then wipes what the
masked tile summed. No dropout and no segment ids, as in the causal family.
"""

from __future__ import annotations

import jax.numpy as jnp

from .flash_causal import (Tile, Walk, attend, causal_walk, pairs,
                           pick_block, strip_cuts, strip_height,
                           supports_causal)


def reach(window: int, blk: int) -> int:
    """How many k blocks behind its own a q block reads."""
    return -(-(window - 1) // blk)


def edge(window: int, blk: int) -> int:
    """The least block distance whose tile holds a pair with ``i - j >=
    window`` (its largest is ``d * blk + blk - 1``): tiles from there on mask
    the band's far side."""
    return max(0, -(-(window - blk + 1) // blk))


def supports_window(L: int, d_qk: int, d_v: int, window: int) -> bool:
    """Shapes the kernels take: the causal family's, and a window that cuts
    something (``1 <= window < L``; at ``window >= L`` it is the causal
    mask)."""
    return supports_causal(L, d_qk, d_v) and 1 <= window < L


def window_walk(L: int, window: int, blk: int, s: int) -> Walk:
    """The band at block edge ``blk``: the diagonal and each far tile in
    strips of ``s`` rows, the tiles between them whole."""
    n, behind, first_far = L // blk, reach(window, blk), edge(window, blk)
    tiles = [Tile(0, 0, strip_cuts(blk, s, 0, window), True,
                  window if first_far == 0 else None)]
    if first_far > 1:
        tiles.append(Tile(1, first_far - 1, ((0, 0, blk),), False, None))
    tiles += [Tile(d, d, strip_cuts(blk, s, d, window), False,
                   window - d * blk)
              for d in range(max(first_far, 1), min(behind, n - 1) + 1)]
    return Walk("flash_window", blk, n, behind, tuple(tiles))


def block_pairs(L: int, window: int) -> tuple:
    """``(walked, causal)``: the pairs one call's grid walks a (row, head)
    under the window, and those the causal triangle holds at the same block
    edge; ``(0, 0)`` for a length no block edge divides."""
    blk = pick_block(L)
    if blk is None:
        return 0, 0
    n = L // blk
    return pairs(n, reach(window, blk), k_outer=False).shape[1], \
        n * (n + 1) // 2


def tile_area(L: int, window, blk, s: int) -> tuple:
    """``(computed, walked)``: the 128 x 128 sub-tiles one call's grid
    computes a (row, head) under its strips of ``s`` rows, and those the
    pairs it walks hold; ``window`` None (or at least ``L``) is the causal
    family's grid; ``(0, 0)`` where no block edge divides ``L``."""
    if blk is None:
        return 0, 0
    if window is None or window >= L:
        return causal_walk(L, blk, s).area()
    return window_walk(L, window, blk, s).area()


def window_attention(q, k, v, mask=None, *, window: int, dtype=jnp.float32,
                     interpret: bool = False):
    """``softmax(q k^T / sqrt(d_qk) + band + key-pad) v`` over ``[B, L, H,
    d_qk]`` q, ``[B, L, H_kv, d_qk]`` k and ``[B, L, H_kv, d_v]`` v (query
    head ``h`` reads key/value head ``h // (H / H_kv)``), the band ``0 <= i -
    j < window``, with a ``[B, L]`` key mask (1 = real); returns ``[B, L, H,
    d_v]`` in ``dtype``."""
    L = q.shape[1]
    blk = pick_block(L)
    walk = window_walk(L, int(window), blk, strip_height(blk))
    return attend(q, k, v, mask, walk, dtype=dtype, interpret=interpret)

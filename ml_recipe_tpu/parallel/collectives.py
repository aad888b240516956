"""Collective primitives.

The reference leaned on NCCL through DDP: implicit gradient all-reduce in
``loss.backward()`` (trainer.py:136-142), barriers, and SyncBN statistics
sync (trainer.py:89-95). Here collectives are explicit XLA ops used inside
``shard_map``/``pjit``-traced functions; XLA lowers them onto ICI/DCN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def pmean(tree, axis_name: str):
    """Mean-reduce a pytree over a mesh axis — the DDP gradient-averaging
    contract (SURVEY.md §7 hard part (e)): DDP averages grads over the world,
    so psum/axis_size keeps the reference's LR advice valid."""
    return jax.tree_util.tree_map(lambda x: lax.pmean(x, axis_name), tree)


def psum_scalar(value, axis_name: str):
    return lax.psum(value, axis_name)


def cross_replica_mean(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Cross-replica moment sync — SyncBN parity (trainer.py:89-95). BERT has
    LayerNorm (no cross-sample stats), so this is exposed as a utility for
    models that do carry BatchNorm-style statistics."""
    return lax.pmean(x, axis_name)


def all_gather(x: jnp.ndarray, axis_name: str, *, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)

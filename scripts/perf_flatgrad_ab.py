#!/usr/bin/env python
"""A/B: flat-gradient plumbing cost in the train step (VERDICT r2 weak #1).

Today's step accumulates gradients as ONE flat f32 vector: each micro-step
ravels+casts ~200 leaves and concatenates (flatten_grads), and the update
path dynamic-slices the clipped vector back into leaves (unflatten_grads).
What this plumbing costs per step is the question.

Variant B differentiates the loss W.R.T. THE FLAT VECTOR itself: params are
unflattened once inside the loss, so reverse-mode writes cotangents directly
into flat-buffer segments — no per-micro concat, no separate accumulate
buffer shuffle. This script times both on whatever backend is visible.

Run on the TPU chip:  python scripts/perf_flatgrad_ab.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel

    class TP:
        loss = "smooth"; smooth_alpha = 0.01; focal_alpha = 1; focal_gamma = 2
        w_start = 1; w_end = 1; w_start_reg = 1; w_end_reg = 1; w_cls = 1

    cfg = MODEL_PRESETS["bert-base-uncased"]
    model = QAModel(cfg, dtype=jnp.bfloat16)
    loss = build_loss(TP())

    B, L, G = 256, 512, 4
    rng = np.random.default_rng(0)
    params = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))["params"]

    inputs = {
        "input_ids": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (G, B // G, L)), jnp.int32
        ),
        "attention_mask": jnp.ones((G, B // G, L), jnp.int32),
        "token_type_ids": jnp.zeros((G, B // G, L), jnp.int32),
    }
    labels = {
        "start_class": jnp.asarray(rng.integers(0, L, (G, B // G)), jnp.int32),
        "end_class": jnp.asarray(rng.integers(0, L, (G, B // G)), jnp.int32),
        "start_reg": jnp.asarray(rng.random((G, B // G)), jnp.float32),
        "end_reg": jnp.asarray(rng.random((G, B // G)), jnp.float32),
        "cls": jnp.asarray(rng.integers(0, 5, (G, B // G)), jnp.int32),
    }

    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(np.prod(l.shape)) if l.ndim else 1 for l in leaves]
    offsets = np.cumsum([0] + sizes)
    total = int(offsets[-1])

    def flatten_tree(tree):
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32)
             for l in jax.tree_util.tree_leaves(tree)]
        )

    def unflatten_vec(vec):
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                jax.lax.dynamic_slice_in_dim(vec, int(offsets[i]), sizes[i])
                .reshape(leaves[i].shape)
                .astype(leaves[i].dtype)
                for i in range(len(leaves))
            ],
        )

    def loss_fn(p, micro_in, micro_lab):
        preds = model.apply({"params": p}, **micro_in, deterministic=True)
        total_, _ = loss(preds, micro_lab)
        return total_

    clip = 1.0

    # -- A: today's scheme — tree grads, flatten+accumulate per micro ------
    def step_a(params, inputs, labels):
        grad_fn = jax.grad(loss_fn)

        def micro(acc, xs):
            mi, ml = xs
            g = grad_fn(params, mi, ml)
            return acc + flatten_tree(g), None

        acc, _ = jax.lax.scan(
            micro, jnp.zeros((total,), jnp.float32), (inputs, labels)
        )
        g = acc * (1.0 / G)
        n = jnp.sqrt(jnp.sum(g * g))
        g = g * (clip / jnp.maximum(n, clip))
        out = unflatten_vec(g)
        # fold into a scalar so timing excludes host transfer of the tree
        return sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(out))

    # -- B: differentiate w.r.t. the flat vector directly -------------------
    flat_params = flatten_tree(params)

    def loss_flat(vec, micro_in, micro_lab):
        return loss_fn(unflatten_vec(vec), micro_in, micro_lab)

    def step_b(flat_params, inputs, labels):
        grad_fn = jax.grad(loss_flat)

        def micro(acc, xs):
            mi, ml = xs
            return acc + grad_fn(flat_params, mi, ml), None

        acc, _ = jax.lax.scan(
            micro, jnp.zeros((total,), jnp.float32), (inputs, labels)
        )
        g = acc * (1.0 / G)
        n = jnp.sqrt(jnp.sum(g * g))
        g = g * (clip / jnp.maximum(n, clip))
        out = unflatten_vec(g)
        return sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(out))

    def bench(fn, *args, steps=8, warmup=2):
        f = jax.jit(fn)
        for _ in range(warmup):
            r = f(*args)
        jax.block_until_ready(r)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            r = f(*args)
            jax.block_until_ready(r)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    ta = bench(step_a, params, inputs, labels)
    tb = bench(step_b, flat_params, inputs, labels)
    print(f"A (tree-grad + flatten/accumulate): {ta*1000:.1f} ms")
    print(f"B (grad wrt flat vector):           {tb*1000:.1f} ms")
    print(f"delta: {(ta-tb)*1000:.1f} ms")


if __name__ == "__main__":
    main()

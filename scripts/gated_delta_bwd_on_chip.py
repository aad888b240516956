"""The chunked delta rule (``ops/gated_delta.py``) on the chip, outside any
cell, at Olmo-Hybrid-7B's head shape (30 heads, ``d_k`` 96, ``d_v`` 192,
bf16 ``q``/``k``/``v``, f32 ``g``/``beta``):

Part 1 (B 1, L ``--grad_len``, 1,024 by default: autodiff of the
token-by-token recurrence keeps a state a token, 2.3 GB there): the operator's
own backward (``custom_vjp``: the chunks in reverse from the kept states)
against ``jax.grad`` of the f32 token-by-token recurrence
(``perfbench/harness/reference_olmo_hybrid.delta_rule``) on the same operands,
for each of the five gradients: maximum absolute difference over the
reference's largest magnitude. The forward against the recurrence at the
cell's L 8,192 beside it.

Part 2 (B 1, L 8,192: one step's row of ``olmo-hybrid-pp8-train-seq8192``):
ms a call of the forward and of forward + backward, the median of
``--repeats`` blocked calls after a warm-up. One JSON line; no fallback to the
CPU (``--rehearse`` is a tiny size on any backend, and prints no time).

    chiprun -- python scripts/gated_delta_bwd_on_chip.py [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def operands(seed, B, L, H, d_k, d_v):
    """Seeded operands as a layer makes them: l2-normalised ``q`` and ``k``,
    ``beta`` in (0, 2), ``g`` from the layer's initialisers."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    A = rng.uniform(1e-4, 16.0, size=H)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(B, L, H)))
    return (jnp.asarray(unit(rng.normal(size=(B, L, H, d_k))), jnp.bfloat16),
            jnp.asarray(unit(rng.normal(size=(B, L, H, d_k))), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(B, L, H, d_v)), jnp.bfloat16),
            jnp.asarray(-A * dt, jnp.float32),
            jnp.asarray(2.0 / (1.0 + np.exp(-rng.normal(size=(B, L, H)))),
                        jnp.float32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=3300000101)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--grad_len", type=int, default=1024)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: 4 heads of 8 / 16 at L 200, no times")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.ops.gated_delta import gated_delta_rule
    from ml_recipe_tpu.utils.platform import configure_compile_cache
    from perfbench.harness.reference_olmo_hybrid import delta_rule

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    H, d_k, d_v, L, grad_len = (4, 8, 16, 200, 72) if args.rehearse \
        else (30, 96, 192, 8192, args.grad_len)
    report = {"device": device.device_kind, "seed": args.seed,
              "heads": H, "d_k": d_k, "d_v": d_v}

    # part 1: the gradients at a length whose autodiff fits, the forward at L
    ops = operands(args.seed, 1, grad_len, H, d_k, d_v)
    weigh = jnp.asarray(operands(args.seed + 1, 1, grad_len, H, d_k, d_v)[2],
                        jnp.float32)
    loss = lambda rule: lambda *a: jnp.sum(  # noqa: E731
        rule(*a).astype(jnp.float32) * weigh)
    got = jax.jit(jax.grad(loss(gated_delta_rule), argnums=range(5)))(*ops)
    want = jax.jit(jax.grad(loss(delta_rule), argnums=range(5)))(*ops)
    report["grad_len"] = grad_len
    report["gradients_max_abs_diff_over_largest"] = {
        name: float(jnp.abs(g.astype(jnp.float32) - w).max()
                    / jnp.abs(w).max())
        for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want)}
    ops = operands(args.seed + 2, 1, L, H, d_k, d_v)
    out = jax.jit(gated_delta_rule)(*ops).astype(jnp.float32)
    ref = jax.jit(delta_rule)(*ops)
    report["forward_len"] = L
    report["forward_max_abs_diff_over_largest"] = float(
        jnp.abs(out - ref).max() / jnp.abs(ref).max())
    report["forward_beyond_one_bf16_rounding_share"] = float(jnp.mean(
        jnp.abs(out - ref) > 2.0 ** -8 * (1.01 * jnp.abs(ref) + 1e-2
                                          * jnp.sqrt(jnp.mean(ref * ref)))))

    # part 2: times at the cell's row
    if not args.rehearse:
        weigh = jnp.ones(out.shape, jnp.float32)
        calls = {"forward_ms": jax.jit(gated_delta_rule),
                 "forward_backward_ms": jax.jit(jax.grad(
                     loss(gated_delta_rule), argnums=range(5)))}
        for name, call in calls.items():
            jax.block_until_ready(call(*ops))
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(call(*ops))
                times.append((time.perf_counter() - t0) * 1e3)
            report[name] = statistics.median(times)
    line = json.dumps(report)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

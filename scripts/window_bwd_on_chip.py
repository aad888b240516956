"""The sliding-window kernels' backward on the chip, outside any cell (a trunk
cell's ``correct`` is forward-only), at Mellum2's heads: 32 query over 4
key/value heads of 128, L 8,192, a window of 1,024.

    chiprun -- python scripts/window_bwd_on_chip.py [--window 1024] [--out FILE]

Part 1 (B 2; the second row's padding starts inside a block): dq, dk, dv of
``flash_window_bwd`` (fused) and of ``flash_window_bwd_dq`` + ``_dkv`` (the
budget forced to 0) against ``_xla_attention(causal=True, window=W)`` in f32
on the same operands, a row and key/value head at a time so that its f32
score matrices fit: maximum absolute difference, and that over the
reference's largest magnitude; the forward output likewise.

Part 2 (B 1, one micro-batch of ``mellum2-ep4-train-seq8192``): ms a call of
the window forward and backward beside the causal forward and backward at the
same shape (the only place the two are compared: no switch goes into the
program), and the window calls at a block edge of 256 beside ``pick_block``'s
512; the median of ``--repeats`` blocked calls after a warm-up. One JSON line;
no fallback to the CPU (``--interpret`` is the rehearsal at a tiny size, and
prints no time).
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=3700000101)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearsal: L 768, 4/2 heads of 64, window 300, "
                         "interpreted, no times")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops import flash_causal as fc
    from ml_recipe_tpu.ops import flash_window as fw
    from ml_recipe_tpu.ops.attention import _xla_attention
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    B, H, H_kv, L, d = (2, 4, 2, 768, 64) if args.interpret \
        else (2, 32, 4, 8192, 128)
    W = args.window or (300 if args.interpret else 1024)
    group = H // H_kv
    real = L - L // 5 - 37                  # inside the last block but one
    rng = np.random.default_rng(args.seed)
    q = jnp.asarray(rng.normal(size=(B, L, H, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, L, H_kv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, L, H_kv, d)), jnp.bfloat16)
    mask = jnp.asarray((np.arange(L)[None, :]
                        < np.array([L, real])[:, None]).astype(np.int32))
    weigh = jnp.asarray(rng.normal(size=(B, L, H, d)), jnp.float32) \
        * mask[:, :, None, None]

    def out_and_grads(attend, weigh):
        def weighed(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * weigh), out
        return jax.jit(jax.grad(weighed, (0, 1, 2), has_aux=True))

    def kernel(q, k, v):
        return fw.window_attention(q, k, v, mask, window=W,
                                   dtype=jnp.bfloat16,
                                   interpret=args.interpret)

    def flat(grads, out):
        return [np.asarray(x, np.float32) for x in (*grads, out)]

    budget = fc._DQ_ROW_BUDGET
    assert fc.fused_backward(L, d)
    fused = flat(*jax.device_get(out_and_grads(kernel, weigh)(q, k, v)))
    fc._DQ_ROW_BUDGET = 0
    split = flat(*jax.device_get(out_and_grads(kernel, weigh)(q, k, v)))
    fc._DQ_ROW_BUDGET = budget

    def plain_of(row, head):
        """XLA in f32 on one row, one key/value head and its query heads."""
        rows, heads = slice(row, row + 1), slice(head, head + 1)
        mine = slice(head * group, (head + 1) * group)
        wide = [x.astype(jnp.float32) for x in (
            q[rows, :, mine], k[rows, :, heads], v[rows, :, heads])]
        return flat(*jax.device_get(out_and_grads(
            lambda q, k, v: _xla_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                mask[rows], causal=True, window=W),
            weigh[rows, :, mine])(*wide)))

    with jax.default_matmul_precision("highest"):
        parts = [[plain_of(row, head) for head in range(H_kv)]
                 for row in range(B)]
    plain = [np.concatenate([np.concatenate([p[i] for p in row], axis=2)
                             for row in parts], axis=0) for i in range(4)]
    real_rows = np.asarray(mask, bool)[:, :, None, None]

    def differ(got, want):
        got, want = got * real_rows, want * real_rows
        gap = float(np.abs(got - want).max())
        return {"max_abs": gap, "over_max": gap / float(np.abs(want).max())}

    names = ("dq", "dk", "dv", "out")
    report = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "shape": {"B": B, "H": H, "H_kv": H_kv, "L": L, "d": d, "window": W,
                  "block": fc.pick_block(L), "real_keys_row_1": real,
                  "dtype": "bfloat16",
                  "block_pairs_walked_and_causal": fw.block_pairs(L, W)},
        "fused_vs_split": {n: differ(f, s) for n, f, s in
                           zip(names, fused, split)},
        "fused_vs_xla_f32": {n: differ(f, p) for n, f, p in
                             zip(names, fused, plain)},
        "split_vs_xla_f32": {n: differ(s, p) for n, s, p in
                             zip(names, split, plain)},
    }

    if not args.interpret:
        B = 1
        shape = lambda heads: jnp.asarray(  # noqa: E731
            rng.normal(size=(B, heads, L, d)), jnp.bfloat16)
        q, k, v, g = shape(H), shape(H_kv), shape(H_kv), shape(H)
        mask3 = jnp.ones((B, 1, L), jnp.int32)

        def ms_a_call(call, operands):
            run = jax.jit(call)
            jax.block_until_ready(run(*operands))
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run(*operands))
                times.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(times)

        def timed(name, tables, fwd, bwd):
            out, lse = jax.jit(fwd)(*tables(False), mask3, q, k, v)
            delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                            axis=-1)[:, :, None, :]
            return {
                f"{name}_fwd": ms_a_call(fwd, (*tables(False), mask3, q, k,
                                               v)),
                f"{name}_bwd": ms_a_call(bwd, (*tables(True), mask3, k, v, q,
                                               g, lse, delta))}

        def window_tables(blk):
            return lambda k_outer: tuple(jnp.asarray(t) for t in fw.pairs(
                L // blk, fw.reach(W, blk), k_outer=k_outer))

        times = {"shape": {"B": B, "H": H, "H_kv": H_kv, "L": L, "d": d,
                           "window": W}}
        times.update(timed(
            "flash_causal", lambda k_outer: fc._tables(L, k_outer=k_outer),
            fc.build_fwd_call(B, H, L, d, d, q.dtype, q.dtype, group=group),
            fc.build_bwd_calls(B, H, L, d, d, q.dtype, group=group)[0]))
        for blk in (fc.pick_block(L), 256):
            times.update(timed(
                f"flash_window_blk{blk}", window_tables(blk),
                fw.build_fwd_call(B, H, L, d, d, W, q.dtype, q.dtype,
                                  group=group, blk=blk),
                fw.build_bwd_calls(B, H, L, d, d, W, q.dtype, group=group,
                                   blk=blk)[0]))
        report["ms_a_call"] = times
    line = json.dumps(report)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()

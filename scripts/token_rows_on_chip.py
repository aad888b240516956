"""The expert layer's three token-side operations on the chip, outside any
cell, in three forms: the depth walks of ``expert_ffn._rows_to_tokens`` (what
the XLA form runs), one fused ``[T, K]`` gather with a masked sum over ``K``
in plain XLA (a control: what XLA alone gives), and the Mosaic kernels of
``ops/token_rows.py``, by token block.

    python scripts/token_rows_on_chip.py [--token_blocks 64 128]   # on a TPU host

At each MoE cell's first chunk (``mellum2-ep4-train-seq8192``: 8,192 tokens,
top-8, 16 of 64 experts held, hidden 2,304, 24,576 rows;
``lfm2-ep4-train-seq8192``: 8,192, top-4, 8 of 32, 2,048, 12,288 rows;
``joyai-ep16-train-seq4096``: 8,192 (two rows of 4,096), top-8, 16 of 256,
2,048, 6,144 rows), each token's experts drawn from ``--seed`` as a router
with a spread of expert popularity picks them (Gumbel top-k over seeded
log-popularities), and the chunk built by ``expert_ffn.make_plan`` /
``_chunk_of`` as the layer builds it.

Per operation (``combine``: the forward weighted sum, float32 out;
``dispatch_bwd``: the unweighted sum of the rows' cotangents, rounded to
bf16; ``d_weights``: the router-weight gradient's dots): ms a call (the mean
of ``--repeats`` calls enqueued back to back and waited for once, the least
of three rounds), the least bytes a single pass needs (each held row read
once, the ``[T, H]`` operand or result once) over 819 GB/s as a share of that
time, and the largest difference from float32 XLA as a share of its largest
value; the kernels' sums also say whether they equal the walks' to the bit.
The kernel form's ``combine`` and ``dispatch_bwd`` include packing the rows
(``pack``, also timed alone); its ``d_weights`` reads the rows the forward
packed. One JSON line, also written to ``--out``; no fallback to the CPU
(``--interpret`` rehearses the control flow at a tiny size and prints no
time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PEAK_BYTES = 819e9
# cell: tokens, top-k, experts held, of, hidden, the spread of expert
# popularity (the standard deviation of its log)
CELLS = {
    "mellum2": (8192, 8, 16, 64, 2304, 0.2),
    "lfm2": (8192, 4, 8, 32, 2048, 0.2),
    "joyai": (8192, 8, 16, 256, 2048, 0.6),
}
TINY = {"tiny": (64, 4, 4, 8, 256, 0.2)}
OPERATIONS = ("combine", "dispatch_bwd", "d_weights")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        ROOT, "results", "token_rows_on_chip.json"))
    ap.add_argument("--seed", type=int, default=4100000101)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--token_blocks", nargs="*", type=int, default=None,
                    help="token blocks of the kernel form (default: the "
                         "pick)")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops import expert_ffn
    from ml_recipe_tpu.ops import token_rows as tr
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    rng = np.random.default_rng(args.seed)
    dtype = jnp.bfloat16

    def ms_a_call(run, operands):
        jax.block_until_ready(run(*operands))
        if args.interpret:
            return None
        rounds = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                out = run(*operands)
            jax.block_until_ready(out)
            rounds.append((time.perf_counter() - t0) * 1e3 / args.repeats)
        return min(rounds)

    # each form: {operation: f(rows, rows_g, w, g, chunk)}, jitted whole
    walks = {
        "combine": lambda r, rg, w, g, c: expert_ffn._rows_to_tokens(r, c, w),
        "dispatch_bwd": lambda r, rg, w, g, c: expert_ffn._rows_to_tokens(
            rg, c).astype(dtype),
        "d_weights": lambda r, rg, w, g, c: expert_ffn._combine_bwd(
            None, (r, c), g)[1]}

    def summed(rows, chunk, weights):
        """One gather of every slot, the depths added in ascending order."""
        parts = rows[chunk.slot_row].astype(jnp.float32)      # [T, K, H]
        acc = jnp.zeros(parts.shape[::2], jnp.float32)
        for j in range(parts.shape[1]):
            part = parts[:, j] if weights is None \
                else parts[:, j] * weights[:, j, None]
            acc = acc + jnp.where(chunk.slot_ok[:, j, None], part, 0.0)
        return acc

    fused = {
        "combine": lambda r, rg, w, g, c: summed(r, c, w),
        "dispatch_bwd": lambda r, rg, w, g, c: summed(rg, c, None).astype(
            dtype),
        "d_weights": lambda r, rg, w, g, c: jnp.where(c.slot_ok, jnp.einsum(
            "th,tkh->tk", g, r[c.slot_row].astype(jnp.float32)), 0.0)}

    def kernels(tb, width):
        kw = dict(dtype=dtype, interpret=args.interpret, tb=tb)

        def total(packed, chunk, weights, out_dtype):
            return tr.token_rows_sum(
                packed, chunk.slot_row, expert_ffn._held(chunk), weights,
                width=width, out_dtype=out_dtype, **kw)

        return {
            "combine": lambda r, rg, w, g, c: total(
                tr.pack(r), c, w, jnp.float32),
            "dispatch_bwd": lambda r, rg, w, g, c: total(
                tr.pack(rg), c, None, dtype),
            # the forward packed the rows: this call reads them so
            "d_weights": lambda p, rg, w, g, c: tr.token_rows_dot(
                g, p, c.slot_row, expert_ffn._held(c), **kw),
            "pack": lambda r, rg, w, g, c: tr.pack(r)}

    def exact(rows, rows_g, w, g, chunk):
        """float32 XLA on the same operands, the sums unrounded."""
        ok = chunk.slot_ok[..., None]
        picked, picked_g = (x.astype(jnp.float32)[chunk.slot_row]
                            for x in (rows, rows_g))
        out = {"combine": jnp.sum(jnp.where(ok, picked * w[..., None], 0.0),
                                  axis=1),
               "dispatch_bwd": jnp.sum(jnp.where(ok, picked_g, 0.0), axis=1),
               "d_weights": jnp.where(chunk.slot_ok, jnp.einsum(
                   "th,tkh->tk", g, picked,
                   precision=jax.lax.Precision.HIGHEST), 0.0)}
        return {k: np.asarray(v, np.float32) for k, v in out.items()}

    report = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "seed": args.seed, "repeats": args.repeats, "cells": {}}
    cells = TINY if args.interpret else CELLS
    for cell in args.cells or cells:
        T, K, count, of, H, spread = cells[cell]
        popularity = rng.normal(size=of) * spread
        gumbel = rng.gumbel(size=(T, of))
        chosen = np.argsort(-(popularity + gumbel), axis=1)[:, :K]
        scores = rng.uniform(0.05, 1.0, size=(T, K)).astype(np.float32)
        plan = expert_ffn.make_plan(jnp.asarray(chosen, jnp.int32),
                                    jnp.asarray(scores), 0, count, of)
        chunk = jax.jit(expert_ffn._chunk_of, static_argnums=(1, 2))(
            plan, 0, plan.capacity)
        C = plan.capacity
        held_rows = int(plan.n_held)
        held = np.asarray(expert_ffn._held(chunk))
        rows = jnp.asarray(rng.normal(size=(C, H)), dtype)
        rows_g = jnp.asarray(rng.normal(size=(C, H)), dtype)
        w = jnp.asarray(np.take_along_axis(
            scores, np.argsort(~np.asarray(plan.held), axis=1, kind="stable"),
            axis=1))
        g = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
        operands = (rows, rows_g, w, g, chunk)
        want = exact(*operands)
        least = {"combine": 2 * held_rows * H + 4 * T * H,
                 "dispatch_bwd": 2 * held_rows * H + 2 * T * H,
                 "d_weights": 2 * held_rows * H + 4 * T * H + 4 * T * K}
        tile = args.token_blocks or [tr.token_block(T, K, H, 2)]
        entry = report["cells"][cell] = {
            "tokens": T, "top_k": K, "rows": C, "held_rows": held_rows,
            "held_a_token": np.bincount(held, minlength=K + 1).tolist(),
            "token_block_picked": tr.token_block(T, K, H, 2), "forms": {}}
        variants = {"walks": walks, "fused_gather": fused}
        variants.update({f"kernel_tb{tb}": kernels(tb, H) for tb in tile})
        packed = jax.jit(tr.pack)(rows)
        sums = {}
        for form, calls in variants.items():
            row = entry["forms"][form] = {}
            for op, call in calls.items():
                run = jax.jit(call)
                given = (packed, *operands[1:]) \
                    if form.startswith("kernel") and op == "d_weights" \
                    else operands
                took = ms_a_call(run, given)
                got = np.asarray(run(*given), np.float32)
                if op == "pack":
                    row[op] = {}
                else:
                    gap = np.abs(got - want[op]).max() / max(
                        np.abs(want[op]).max(), 1e-30)
                    row[op] = {"error_over_max": float(gap)}
                    if op != "d_weights":
                        sums.setdefault(op, {})[form] = got
                if took is not None:
                    row[op]["ms"] = took
                    if op != "pack":
                        row[op]["hbm_share"] = least[op] / PEAK_BYTES / (
                            took * 1e-3)
                print(cell, form, op, row[op], file=sys.stderr, flush=True)
            if "ms" in row.get("combine", {}):
                row["three_ms"] = sum(row[op]["ms"] for op in OPERATIONS)
        for op, by_form in sums.items():
            entry.setdefault("bit_equal_to_walks", {})[op] = {
                form: bool(np.array_equal(got, by_form["walks"]))
                for form, got in by_form.items() if form != "walks"}
    line = json.dumps(report)
    print(line)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()

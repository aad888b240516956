"""The expert layers' six grouped matmuls on the chip, outside any cell, in
both forms: ``jax.lax.ragged_dot`` (what the TPU compiler makes of it) and the
Mosaic kernels of ``ops/grouped_matmul.py``, by row tile.

    chiprun -- python scripts/grouped_matmul_on_chip.py [--row_tiles 128 256 512]

At each MoE cell's shapes (``mellum2-ep4-train-seq8192``: 16 experts, hidden
2,304, width 896, a first chunk of 24,576 rows and a granule of 4,096;
``lfm2-ep4-train-seq8192``: 8, 2,048, 1,792, 12,288 / 2,048;
``joyai-ep16-train-seq4096``: 16, 2,048, 768, 6,144 / 1,024), group sizes
drawn from ``--seed`` around the expectation at the cell's measured spread
(the fullest held expert over the mean: 1.22 / 1.25 / 2.78), the first
chunk's tail filler; a granule holds rows of its last two groups only.

Per matmul (``gate_up``: rows [M, H] x [E, H, 2F]; ``down``: [M, F] x [E, F,
H]) and call (forward, d-rows, d-weights): ms a call (the mean of
``--repeats`` calls enqueued back to back and waited for once, the least of
three rounds, so the host's dispatch is hidden behind the device), the needed
FLOPs (2 x held rows x K x N) over 197 TFLOP/s and the least bytes over 819
GB/s as shares of that time, and the largest difference from float32 XLA at
HIGHEST on the same bf16 operands as a share of the reference's largest
value (over the groups' rows; what a form leaves in the rows past the last
group is read apart). One JSON line, also written to ``--out``; a granule is
read in ``ragged_dot`` and at the picked row tile only; no fallback to the CPU
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

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
# cell: experts held, hidden, expert width, first chunk, granule, rows an
# expert expects, the fullest held expert over the mean (ledger, PR 37)
CELLS = {
    "mellum2": (16, 2304, 896, 24576, 4096, 1024, 1.22),
    "lfm2": (8, 2048, 1792, 12288, 2048, 1024, 1.25),
    "joyai": (16, 2048, 768, 6144, 1024, 256, 2.78),
}
TINY = {"tiny": (4, 128, 128, 96, 32, 16, 1.5)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "grouped_matmul_on_chip.json"))
    ap.add_argument("--seed", type=int, default=3800000101)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--row_tiles", nargs="*", type=int, default=None,
                    help="row tiles of the kernel form (default: the pick)")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops import grouped_matmul as gm
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    rng = np.random.default_rng(args.seed)
    dtype = jnp.bfloat16

    def sizes_of(groups, expected, spread):
        """Rows a group around ``expected``, the fullest ``spread`` x the
        mean."""
        u = rng.normal(size=groups)
        u = u - u.mean()
        w = np.clip(1.0 + (spread - 1.0) * u / u.max(), 0.05, None)
        return np.round(w / w.mean() * expected).astype(np.int32)

    def ms_a_call(run, operands):
        if args.interpret:
            jax.block_until_ready(run(*operands))
            return None
        jax.block_until_ready(run(*operands))
        rounds = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                out = run(*operands)
            jax.block_until_ready(out)
            rounds.append((time.perf_counter() - t0) * 1e3 / args.repeats)
        return min(rounds)

    def ragged(r, w, s):
        return jax.lax.ragged_dot(r, w, s, preferred_element_type=r.dtype)

    def kernel_at(tm):
        return lambda r, w, s: gm._kernels(r, w, s, tm, args.interpret)

    def calls_of(matmul):
        """``{call: jitted}``: the matmul alone, and each of its two
        gradients alone (the other is dead code)."""
        def grads(r, w, s, g):
            return jax.vjp(lambda r, w: matmul(r, w, s), r, w)[1](g)

        return {"fwd": jax.jit(lambda r, w, s, g: matmul(r, w, s)),
                "drows": jax.jit(lambda r, w, s, g: grads(r, w, s, g)[0]),
                "dweights": jax.jit(lambda r, w, s, g: grads(r, w, s, g)[1])}

    def exact(r, w, s, g):
        """float32 XLA at HIGHEST on the same operands."""
        wide = [x.astype(jnp.float32) for x in (r, w, g)]

        def run(r, w, g):
            out, vjp = jax.vjp(lambda r, w: jax.lax.ragged_dot(
                r, w, s, precision=jax.lax.Precision.HIGHEST), r, w)
            return (out, *vjp(g))

        return [np.asarray(x) for x in jax.jit(run)(*wide)]

    report = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "seed": args.seed, "repeats": args.repeats, "cells": {}}
    cells = TINY if args.interpret else CELLS
    for cell in args.cells or cells:
        groups, H, F, capacity, granule, expected, spread = cells[cell]
        pick = gm.row_tile(capacity, expected)
        tiles = args.row_tiles or [pick]
        first = sizes_of(groups, expected, spread)
        tail = np.zeros(groups, np.int32)
        tail[-2:] = granule * 3 // 10, granule // 2
        entry = report["cells"][cell] = {
            "row_tile_picked": pick, "sizes": first.tolist(),
            "fullest_over_mean": float(first.max() / first.mean()),
            "chunks": {}}
        for chunk, (M, sizes) in {"first": (capacity, first),
                                  "granule": (granule, tail)}.items():
            held = int(sizes.sum())
            s = jnp.asarray(sizes)
            fill = {tm: float(gm.tile_fill(jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(s)]), tm))
                for tm in {*tiles, pick}}
            rows_out = entry["chunks"][chunk] = {
                "rows": M, "held": held, "row_tile_fill": fill, "matmuls": {}}
            for name, (K, N) in {"gate_up": (H, 2 * F),
                                 "down": (F, H)}.items():
                r = jnp.asarray(rng.normal(size=(M, K)), dtype)
                r = r * (jnp.arange(M) < held)[:, None].astype(dtype)
                w = jnp.asarray(rng.normal(size=(groups, K, N)) * 0.02, dtype)
                g = jnp.asarray(rng.normal(size=(M, N)), dtype)
                operands = (r, w, s, g)
                want = exact(*operands)
                flops = 2.0 * held * K * N
                # every call reads two of rows, weights, result and writes
                # the third: the same least bytes (bf16)
                least = 2 * (held * K + groups * K * N + held * N)
                table = rows_out["matmuls"][name] = {"K": K, "N": N}
                variants = {"ragged_dot": ragged}
                for tm in tiles if chunk == "first" else [pick]:
                    variants[f"kernel_tm{tm}"] = kernel_at(tm)
                for form, matmul in variants.items():
                    row = table[form] = {}
                    for at, (call, run) in enumerate(
                            calls_of(matmul).items()):
                        took = ms_a_call(run, operands)
                        got = np.asarray(run(*operands), np.float32)
                        # the rows of the groups: what lies past them is no
                        # result (read apart: zeros by the contract)
                        rows = slice(None) if call == "dweights" \
                            else slice(0, held)
                        gap = float(np.abs(got - want[at])[rows].max()
                                    / np.abs(want[at][rows]).max())
                        row[call] = {"error_over_max": gap}
                        if call != "dweights":
                            row[call]["past_the_groups_max_abs"] = float(
                                np.abs(np.nan_to_num(
                                    got[held:], nan=np.inf)).max())
                        if took is not None:
                            row[call].update(
                                ms=took,
                                flops_share=flops / PEAK_FLOPS / (took * 1e-3),
                                bytes_share=least / PEAK_BYTES
                                / (took * 1e-3))
                        print(cell, chunk, name, form, call, row[call],
                              file=sys.stderr, flush=True)
    line = json.dumps(report)
    print(line)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()

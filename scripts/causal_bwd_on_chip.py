"""The causal two-width backward on the chip, outside any cell: the fused
kernel against the two-kernel backward and against plain XLA attention at the
published MLA widths, then the kernels' times at the cell's micro-batch.

    chiprun -- python scripts/causal_bwd_on_chip.py [--out FILE]
    chiprun -- python scripts/causal_bwd_on_chip.py --gqa    (32/8 heads of
        64 at L 8,192: ``lfm2-ep4-train-seq8192``'s attention layer)

Part 1 (B 2, H 4, L 4,096, d 192/128, bf16; the second row's padding starts
inside a block): dq, dk, dv of ``flash_causal_bwd`` against
``flash_causal_bwd_dq`` + ``_dkv`` (the budget forced to 0) and against
``_xla_attention(causal=True)`` in f32 on the same operands: maximum absolute
difference, and that over the reference's largest magnitude.

Part 2 (B 2, H 32: one micro-batch of ``joyai-ep16-train-seq4096``): ms a
call of the forward, the fused backward and the split backward, the median of
``--repeats`` blocked calls after a warm-up. One JSON line; no fallback to the
CPU (``--interpret`` is the rehearsal at a tiny size, and prints no time).

``--gqa``: both parts at 32 query heads over 8 key/value heads of 64, L 8,192
(k and v enter with 8 heads; the XLA reference repeats them and is computed a
key/value head at a time, so that its f32 score matrices fit).
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
    ap.add_argument("--seed", type=int, default=2800000101)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearsal: L 768, H 2, interpreted, no times")
    ap.add_argument("--gqa", action="store_true",
                    help="32/8 heads of 64 at L 8,192 (4/2 interpreted)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops import flash_causal as fc
    from ml_recipe_tpu.ops.attention import _xla_attention
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    B, H, L, d_qk, d_v = (2, 2, 768, 192, 128) if args.interpret \
        else (2, 4, 4096, 192, 128)
    H_kv = H
    if args.gqa:
        B, H, H_kv, L, d_qk, d_v = (2, 4, 2, 768, 64, 64) if args.interpret \
            else (2, 32, 8, 8192, 64, 64)
    group = H // H_kv
    real = L - L // 5 - 37                  # inside the last block but one
    rng = np.random.default_rng(args.seed)
    q = jnp.asarray(rng.normal(size=(B, L, H, d_qk)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, L, H_kv, d_qk)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, L, H_kv, d_v)), jnp.bfloat16)
    mask = jnp.asarray((np.arange(L)[None, :]
                        < np.array([L, real])[:, None]).astype(np.int32))
    weigh = jnp.asarray(rng.normal(size=(B, L, H, d_v)), jnp.float32) \
        * mask[:, :, None, None]

    def grads_of(attend):
        def weighed(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) * weigh)
        return jax.jit(jax.grad(weighed, (0, 1, 2)))

    def kernel(q, k, v):
        return fc.causal_attention(q, k, v, mask, dtype=jnp.bfloat16,
                                   interpret=args.interpret)

    budget = fc._DQ_ROW_BUDGET
    assert fc.fused_backward(L, d_qk)
    fused = jax.device_get(grads_of(kernel)(q, k, v))
    fc._DQ_ROW_BUDGET = 0
    split = jax.device_get(grads_of(kernel)(q, k, v))
    fc._DQ_ROW_BUDGET = budget
    def plain_of(heads):
        """XLA in f32 on key/value heads ``heads`` and their query heads."""
        nonlocal weigh
        whole, weigh = weigh, weigh[:, :, heads.start * group:
                                    heads.stop * group]
        wide = [x.astype(jnp.float32) for x in (
            q[:, :, heads.start * group:heads.stop * group], k[:, :, heads],
            v[:, :, heads])]
        got = jax.device_get(grads_of(lambda q, k, v: _xla_attention(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
            mask, causal=True))(*wide))
        weigh = whole
        return got

    parts = [plain_of(slice(h, h + 1)) for h in range(H_kv)] if args.gqa \
        else [plain_of(slice(0, H_kv))]
    plain = [np.concatenate([p[i] for p in parts], axis=2) for i in range(3)]

    def differ(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        gap = float(np.abs(got - want).max())
        return {"max_abs": gap, "over_max": gap / float(np.abs(want).max()),
                "elements_differing": int((got != want).sum())}

    report = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "shape": {"B": B, "H": H, "H_kv": H_kv, "L": L, "d_qk": d_qk,
                  "d_v": d_v, "real_keys_row_1": real, "dtype": "bfloat16"},
        "fused_vs_split": {n: differ(f, s) for n, f, s in
                           zip(("dq", "dk", "dv"), fused, split)},
        "fused_vs_xla_f32": {n: differ(f, p) for n, f, p in
                             zip(("dq", "dk", "dv"), fused, plain)},
        "split_vs_xla_f32": {n: differ(s, p) for n, s, p in
                             zip(("dq", "dk", "dv"), split, plain)},
    }

    if not args.interpret:
        B, H = 2, 32
        H_kv = H // group
        shape = lambda heads, d: jnp.asarray(  # noqa: E731
            rng.normal(size=(B, heads, L, d)), jnp.bfloat16)
        q, k, g = shape(H, d_qk), shape(H_kv, d_qk), shape(H, d_v)
        v = shape(H_kv, d_v)
        mask3 = jnp.ones((B, 1, L), jnp.int32)
        out, lse = jax.jit(fc.build_fwd_call(
            B, H, L, d_qk, d_v, q.dtype, q.dtype, group=group))(
            *fc._tables(L, k_outer=False), mask3, q, k, v)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, :, None, :]
        kv_major = (*fc._tables(L, k_outer=True), mask3, k, v, q, g, lse,
                    delta)
        q_major = (*fc._tables(L, k_outer=False), mask3, q, k, v, g, lse,
                   delta)

        def ms_a_call(call, operands):
            run = jax.jit(call)
            jax.block_until_ready(run(*operands))
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run(*operands))
                times.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(times)

        (bwd,) = fc.build_bwd_calls(B, H, L, d_qk, d_v, q.dtype, group=group)
        fc._DQ_ROW_BUDGET = 0
        dq_call, dkv_call = fc.build_bwd_calls(B, H, L, d_qk, d_v, q.dtype,
                                               group=group)
        fc._DQ_ROW_BUDGET = budget
        report["ms_a_call"] = {
            "shape": {"B": B, "H": H, "H_kv": H_kv, "L": L},
            "flash_causal_fwd": ms_a_call(
                fc.build_fwd_call(B, H, L, d_qk, d_v, q.dtype, q.dtype,
                                  group=group),
                q_major[:6]),
            "flash_causal_bwd": ms_a_call(bwd, kv_major),
            "flash_causal_bwd_dq": ms_a_call(dq_call, q_major),
            "flash_causal_bwd_dkv": ms_a_call(dkv_call, kv_major),
        }
    line = json.dumps(report)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()

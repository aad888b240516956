"""The chunked delta rule (``ops/gated_delta.py``) on the chip, outside any
cell, at Olmo-Hybrid-7B's head shape (30 heads, ``d_k`` 96, ``d_v`` 192,
bf16 ``q``/``k``/``v``, f32 ``g``/``beta``), in BOTH its forms: ``xla`` (the
plain form, the oracle) and ``kernel`` (``ops/gated_delta_kernel.py``, the
form a TPU runs at this shape).

Part 1 (B 1, L ``--grad_len``, 1,024 by default: autodiff of the
token-by-token recurrence keeps a state a token, 2.3 GB there): each form's
backward (``custom_vjp``: the chunks in reverse from the kept states) against
``jax.grad`` of the f32 token-by-token recurrence
(``perfbench/harness/reference_olmo_hybrid.delta_rule``) on the same operands,
for each of the five gradients: maximum absolute difference over the
reference's largest magnitude. At the cell's L 8,192 beside it: each form's
forward against the recurrence (part (b) of the cell's ``correct``: the share
of outputs further than one bf16 rounding), and the kernel's five gradients
against the XLA form's ``custom_vjp`` (no autodiff temporaries, so it fits).

Part 2 (B 1, L 8,192: one step's row of ``olmo-hybrid-pp8-train-seq8192``):
ms a call of each form's forward and forward + backward, the median of
``--repeats`` blocked calls after a warm-up; then one call of each under
``jax.profiler``, its device self time split by what the events are
(``device_ms_by_part``: the triangular solve, the ``while`` walks' own time,
the Mosaic calls, copies and transposes, the other fusions, those inside a
walk among them). One JSON line; no fallback
to the CPU (``--rehearse`` is a tiny size on any backend with the kernels
interpreted, and prints no time).

    chiprun -- python scripts/gated_delta_bwd_on_chip.py [--out FILE]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import tempfile
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


def device_ms_by_part(call, ops, log_dir):
    """Device milliseconds of ONE blocked ``call(*ops)`` under
    ``jax.profiler``, by what its events are."""
    import glob

    import jax

    parts = (("solve", ("triangular", "TriangularSolve")),
             ("walk", ("while",)),
             ("kernel", ("gated_delta",)),
             ("copies", ("copy", "transpose")))
    jax.profiler.start_trace(log_dir)
    jax.block_until_ready(call(*ops))
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            # self time: a ``while`` holds its body's events
            open_events = []        # [end, part, self ns]
            for event in sorted(line.events, key=lambda e: e.start_ns):
                while open_events and open_events[-1][0] <= event.start_ns:
                    _, part, ns = open_events.pop()
                    out[part] = out.get(part, 0.0) + ns * 1e-6
                if open_events:
                    open_events[-1][2] -= event.duration_ns
                open_events.append([
                    event.start_ns + event.duration_ns,
                    next((part for part, marks in parts
                          if any(m in event.name for m in marks)), "other"),
                    event.duration_ns])
            for _, part, ns in open_events:
                out[part] = out.get(part, 0.0) + ns * 1e-6
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=3300000101)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--grad_len", type=int, default=1024)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: 4 heads of 32 / 64 at L 200, the "
                         "kernels interpreted, no times")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.ops import gated_delta
    from ml_recipe_tpu.utils.platform import configure_compile_cache
    from perfbench.harness.reference_olmo_hybrid import delta_rule

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): nothing is measured")
    H, d_k, d_v, L, grad_len = (4, 32, 64, 200, 72) if args.rehearse \
        else (30, 96, 192, 8192, args.grad_len)
    row = lambda d: jax.ShapeDtypeStruct((1, L, H, d), jnp.bfloat16)  # noqa: E731
    report = {"device": device.device_kind, "seed": args.seed,
              "heads": H, "d_k": d_k, "d_v": d_v,
              "form_chosen": "xla" if gated_delta.kernel_mode(
                  row(d_k), row(d_v)) is None else "kernel"}
    forms = {form: functools.partial(
        lambda mode, *a: gated_delta._gated_delta(
            *a, gated_delta.CHUNK, mode), mode)
        for form, mode in (("xla", None), ("kernel", args.rehearse))}
    loss = lambda rule, weigh: lambda *a: jnp.sum(  # noqa: E731
        rule(*a).astype(jnp.float32) * weigh)
    grads = lambda rule, weigh: jax.jit(jax.grad(  # noqa: E731
        loss(rule, weigh), argnums=range(5)))
    distances = lambda got, want: {  # noqa: E731
        name: float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)
                            ).max() / jnp.abs(w.astype(jnp.float32)).max())
        for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want)}

    # part 1: the gradients at a length whose autodiff fits, the forward at L
    ops = operands(args.seed, 1, grad_len, H, d_k, d_v)
    weigh = jnp.asarray(operands(args.seed + 1, 1, grad_len, H, d_k, d_v)[2],
                        jnp.float32)
    want = grads(delta_rule, weigh)(*ops)
    report["grad_len"] = grad_len
    report["gradients_max_abs_diff_over_largest"] = {
        form: distances(grads(rule, weigh)(*ops), want)
        for form, rule in forms.items()}
    del want
    ops = operands(args.seed + 2, 1, L, H, d_k, d_v)
    ref = jax.jit(delta_rule)(*ops)
    report["forward_len"] = L
    for form, rule in forms.items():
        out = jax.jit(rule)(*ops).astype(jnp.float32)
        report.setdefault("forward_max_abs_diff_over_largest", {})[form] = \
            float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
        report.setdefault("forward_beyond_one_bf16_rounding_share", {})[
            form] = float(jnp.mean(
                jnp.abs(out - ref) > 2.0 ** -8 * (
                    1.01 * jnp.abs(ref) + 1e-2 * jnp.sqrt(
                        jnp.mean(ref * ref)))))
    weigh = jnp.asarray(operands(args.seed + 3, 1, L, H, d_k, d_v)[2],
                        jnp.float32)
    report["kernel_gradients_against_the_xla_forms_at_forward_len"] = \
        distances(grads(forms["kernel"], weigh)(*ops),
                  grads(forms["xla"], weigh)(*ops))

    # part 2: times at the cell's row
    if not args.rehearse:
        for form, rule in forms.items():
            calls = {"forward_ms": jax.jit(rule),
                     "forward_backward_ms": grads(rule, weigh)}
            for name, call in calls.items():
                jax.block_until_ready(call(*ops))
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(call(*ops))
                    times.append((time.perf_counter() - t0) * 1e3)
                report.setdefault(name, {})[form] = statistics.median(times)
                with tempfile.TemporaryDirectory() as log_dir:
                    report.setdefault("device_ms_by_part", {})[
                        f"{form}.{name[:-3]}"] = device_ms_by_part(
                            call, ops, log_dir)
    line = json.dumps(report)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

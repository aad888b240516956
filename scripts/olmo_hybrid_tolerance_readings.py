"""The readings behind ``perfbench/harness/checks_olmo_hybrid.py``'s limits,
on the chip at the published widths (PERF.md section 4 records them). Per
seed, on the benchmark's ragged seeded rows at 8,192 and seeded weights, the
verdict of ``checks_olmo_hybrid.compare`` itself on:

1. the system: bf16 matmuls, the timed causal kernels, the chunked delta rule
   with its f32 state, solve and products, in the form the cell runs (the
   Mosaic kernels on a TPU);
2. ``bf16_state``: the system with the state rounded to bf16 between chunks
   (the configuration states f32);
3. ``bf16_solve``: the system with what enters the unit-triangular solve and
   what it gives rounded to bf16 (the configuration states f32); both on the
   rule's XLA form, whose ``_advance`` and ``_solve`` a wrapper can lower;
4. ``beta_without_2``: the system with ``beta = sigmoid`` and not
   ``2 sigmoid`` (``linear_allow_neg_eigval`` dropped);
5. ``no_decay``: the system with ``g = 0`` (the state never decays);
6. ``no_l2norm``: the system with q and k as the convolution's SiLU gives
   them, not l2-normalised;
7. ``float8_matmuls``: the reference with every projection's and FFN's matmul
   inputs rounded to float8_e4m3, the nearest precision below the stated
   bf16.

Each limit has to lie above every reading of (1) and below one of (2)-(7),
with room on both sides; ``failed_parts`` says which limit caught a control.
The lowered arithmetic is ``joyai_tolerance_readings.py``'s.

    chiprun -- python scripts/olmo_hybrid_tolerance_readings.py --seeds 11 12
"""

import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "scripts")]

CELL = "olmo-hybrid-pp8-train-seq8192"


def controls(model, cfg, tile):
    """``{name: system}`` for ``checks_olmo_hybrid.compare(system=...)``."""
    import jax.numpy as jnp

    from joyai_tolerance_readings import lowered_arithmetic, patched
    from ml_recipe_tpu.models import mla_moe
    from ml_recipe_tpu.ops import gated_delta
    from perfbench.harness import checks_olmo_hybrid, reference_olmo_hybrid

    program = checks_olmo_hybrid.program(model)
    lowered = lowered_arithmetic(tile)
    bf16 = lowered["bf16"]
    advance, solve = gated_delta._advance, gated_delta._solve

    def advance_to_a_bf16_state(M, *parts):
        M_next, out = advance(M, *parts)
        return bf16(M_next), out

    def on_the_xla_form(name, lowered):
        return patched(gated_delta, "kernel_mode", lambda *widths: None,
                       patched(gated_delta, name, lowered, program))

    def reference(p, inputs):
        preds, own = reference_olmo_hybrid.forward(p, cfg, **inputs)
        return preds, own["scan"]

    return {
        "bf16_state": on_the_xla_form("_advance", advance_to_a_bf16_state),
        "bf16_solve": on_the_xla_form(
            "_solve", lambda A, rhs: bf16(solve(bf16(A), bf16(rhs)))),
        "beta_without_2": checks_olmo_hybrid.program(dataclasses.replace(
            model, cfg=dataclasses.replace(
                model.cfg, linear_allow_neg_eigval=False))),
        "no_decay": patched(
            mla_moe, "_log_decay",
            lambda a, A_log, dt_bias: jnp.zeros_like(a), program),
        "no_l2norm": patched(mla_moe, "_l2norm", lambda x: x, program),
        "float8_matmuls": patched(reference_olmo_hybrid, "_matmul",
                                  lowered["matmul_in_float8"], reference),
    }


def main(argv=None) -> int:
    import jax

    from joyai_tolerance_readings import arguments, verdicts_by_seed
    from perfbench.harness import checks_olmo_hybrid

    args = arguments(__doc__, argv)
    for seed, verdicts, *_ in verdicts_by_seed(
            args, CELL, checks_olmo_hybrid.compare, controls):
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "verdicts": verdicts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The readings behind ``perfbench/harness/checks_lfm2.py``'s limits, on the
chip at the published widths (PERF.md section 4 records them). Per seed, on
the benchmark's ragged seeded rows at 8,192 and seeded weights, the verdict
of ``checks_lfm2.compare`` itself on:

1. the system: bf16 matmuls, the timed kernels, f32 gating and taps;
2. ``bf16_router``: the system with its router computed in bf16 (the
   configuration states f32);
3. ``float8_matmuls``: the reference with every matmul's inputs rounded to
   float8_e4m3, the nearest precision below the stated bf16;
4. ``bf16_partial_sums``: the reference with bf16 matmul inputs and the sum
   over the contracted axis kept in bf16 between tiles of 128 (the
   configuration states f32 accumulation);
5. ``bf16_gating``: the reference with bf16 matmul inputs and every
   elementwise result of the short convolution rounded to bf16 (the
   configuration states f32 gating and taps, rounded once).

Each limit has to lie above every reading of (1) and below one of (2)-(5),
with room on both sides; ``failed_parts`` says which limit caught a control.
The lowered arithmetic is ``joyai_tolerance_readings.py``'s.

    chiprun -- python scripts/lfm2_tolerance_readings.py --seeds 11 12
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "scripts")]

CELL = "lfm2-ep4-train-seq8192"


def controls(model, cfg, tile):
    """``{name: system}`` for ``checks_lfm2.compare(system=...)``."""
    from joyai_tolerance_readings import lowered_arithmetic, patched
    from ml_recipe_tpu.models import mla_moe
    from perfbench.harness import checks_lfm2, reference_lfm2

    program = checks_lfm2.program(model)
    lowered = lowered_arithmetic(tile)
    bf16 = lowered["bf16"]

    def reference(p, inputs):
        preds, own = reference_lfm2.forward(p, cfg, **inputs)
        return preds, own["chosen"], own["router_input"], own["conv"]

    def matmul_in_bf16(x, w):
        return bf16(x) @ bf16(w)

    return {
        "bf16_router": patched(mla_moe, "_router_scores",
                               lowered["scores_in_bf16"], program),
        "float8_matmuls": patched(reference_lfm2, "_matmul",
                                  lowered["matmul_in_float8"], reference),
        "bf16_partial_sums": patched(
            reference_lfm2, "_matmul", lowered["matmul_bf16_partial_sums"],
            reference),
        "bf16_gating": patched(
            reference_lfm2, "_gating", bf16,
            patched(reference_lfm2, "_matmul", matmul_in_bf16, reference)),
    }


def main(argv=None) -> int:
    import jax

    from joyai_tolerance_readings import arguments, verdicts_by_seed
    from perfbench.harness import checks_lfm2

    args = arguments(__doc__, argv)
    for seed, verdicts, *_ in verdicts_by_seed(
            args, CELL, checks_lfm2.compare, controls):
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "verdicts": verdicts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

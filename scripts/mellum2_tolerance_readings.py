"""The readings behind ``perfbench/harness/checks_mellum2.py``'s limits, on
the chip at the published widths (PERF.md section 4 records them). Per seed,
on the benchmark's ragged seeded rows at 8,192 and seeded weights, the verdict
of ``checks_mellum2.compare`` itself on:

1. the system: bf16 matmuls, the timed window and causal kernels;
2. ``no_window``: the system with the window left out of its
   ``sliding_attention`` layers (a causal mask);
3. ``no_yarn``: the system with the sliding layers' rotation in its
   ``full_attention`` layer;
4. ``bf16_softmax``: the system with its attention cores computed by the
   reference's blocked form under a softmax whose logits, exponentials, sum
   and quotient are each rounded to bf16 (the configuration states f32);
5. ``bf16_router``: the system with its router's logits and softmax in bf16;
6. ``float8_matmuls``: the reference with every matmul's inputs rounded to
   float8_e4m3, the nearest precision below the stated bf16;
7. ``bf16_partial_sums``: the reference with bf16 matmul inputs and the sum
   over the contracted axis kept in bf16 between tiles of 128.

Each limit has to lie above every reading of (1) and below one of (2)-(7),
with room on both sides; ``failed_parts`` says which limit caught a control.
The lowered arithmetic is ``joyai_tolerance_readings.py``'s.

    chiprun -- python scripts/mellum2_tolerance_readings.py --seeds 11 12
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "scripts")]

CELL = "mellum2-ep4-train-seq8192"


def controls(model, cfg, tile):
    """``{name: system}`` for ``checks_mellum2.compare(system=...)``."""
    import jax
    import jax.numpy as jnp
    from joyai_tolerance_readings import lowered_arithmetic, patched
    from ml_recipe_tpu.models import mla_moe
    from perfbench.harness import checks_mellum2, reference_mellum2

    program = checks_mellum2.program(model)
    lowered = lowered_arithmetic(tile)
    bf16 = lowered["bf16"]
    attention, frequencies = (mla_moe.dot_product_attention,
                              mla_moe.pair_frequencies)

    def reference(p, inputs):
        preds, own = reference_mellum2.forward(p, cfg, **inputs)
        return preds, own["chosen"], own["router_input"], own["attention"]

    def causal_only(*operands, window=None, **how):
        return attention(*operands, **how)

    def plain_rotation(cfg, kind, d):
        return frequencies(cfg, "sliding_attention", d)

    def softmax_in_bf16(scores):
        s = bf16(scores)
        e = bf16(jnp.exp(bf16(s - jnp.max(s, axis=-1, keepdims=True))))
        return bf16(e / bf16(jnp.sum(e, axis=-1, keepdims=True)))

    def core_in_bf16(q, k, v, mask, *, dtype, window=None, **_):
        core = patched(
            reference_mellum2, "_softmax", softmax_in_bf16,
            lambda _, operands: reference_mellum2.attention_core(*operands))
        with jax.default_matmul_precision("highest"):
            return core(None, (q, k, v, mask, window)).astype(dtype)

    def probabilities_in_bf16(x, kernel):
        return bf16(jax.nn.softmax(bf16(bf16(x) @ bf16(kernel)), axis=-1))

    return {
        "no_window": patched(mla_moe, "dot_product_attention", causal_only,
                             program),
        "no_yarn": patched(mla_moe, "pair_frequencies", plain_rotation,
                           program),
        "bf16_softmax": patched(mla_moe, "dot_product_attention",
                                core_in_bf16, program),
        "bf16_router": patched(mla_moe, "_router_probabilities",
                               probabilities_in_bf16, program),
        "float8_matmuls": patched(reference_mellum2, "_matmul",
                                  lowered["matmul_in_float8"], reference),
        "bf16_partial_sums": patched(
            reference_mellum2, "_matmul", lowered["matmul_bf16_partial_sums"],
            reference),
    }


def main(argv=None) -> int:
    import jax

    from joyai_tolerance_readings import arguments, verdicts_by_seed
    from perfbench.harness import checks_mellum2

    args = arguments(__doc__, argv)
    for seed, verdicts, *_ in verdicts_by_seed(
            args, CELL, checks_mellum2.compare, controls):
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "verdicts": verdicts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The readings behind ``perfbench/harness/checks_joyai.py``'s limits, on the
chip at the published widths (PERF.md section 4 records them). Per seed, on
the benchmark's ragged seeded rows at 4,096 and seeded weights, the verdict
of ``checks_joyai.compare`` itself on:

1. the system: bf16 matmuls and the timed kernels;
2. ``bf16_router``: the system with its router computed in bf16 (state,
   kernel and scores rounded to 8 bits; the configuration states f32);
3. ``float8_matmuls``: the reference with every matmul's inputs rounded to
   float8_e4m3, the nearest precision below the stated bf16;
4. ``bf16_partial_sums``: the reference with bf16 matmul inputs and the sum
   over the contracted axis kept in bf16 between tiles of 128 (the
   configuration states f32 accumulation).

Each limit has to lie above every reading of (1) and below one of (2)-(4),
with room on both sides; ``failed_parts`` says which limit caught a control.
Beside them, what no limit of a logit can show: whether the grouped matmuls
(``ops/grouped_matmul.py`` in the form a cell runs: the Mosaic kernels on the
chip) equal ``jax.lax.ragged_dot``'s float32 result rounded once, and the load
of the held experts with the states' common component taken out.

    chiprun -- python scripts/joyai_tolerance_readings.py --seeds 11 12
"""

import argparse
import functools
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

TILE = 128      # rows of the contracted axis the matrix unit sums exactly


def patched(module, name, replacement, run):
    """``run`` with ``module.name`` replaced while it is traced."""
    def system(p, inputs):
        kept = getattr(module, name)
        setattr(module, name, replacement)
        try:
            return run(p, inputs)
        finally:
            setattr(module, name, kept)
    return system


def lowered_arithmetic(tile=TILE):
    """``{name: function}``: a rounding to bf16, a router's scores in bf16,
    a matmul on float8 inputs, a matmul whose sum over the contracted axis
    is kept in bf16 between tiles of ``tile``."""
    import jax
    import jax.numpy as jnp

    # ``reduce_precision`` and not a cast there and back: on the chip XLA may
    # skip a rounding between two float32 values (excess precision)
    def rounded(a, exponent_bits, mantissa_bits):
        return jax.lax.reduce_precision(
            jnp.asarray(a, jnp.float32), exponent_bits, mantissa_bits)

    bf16 = functools.partial(rounded, exponent_bits=8, mantissa_bits=7)
    float8 = functools.partial(rounded, exponent_bits=4, mantissa_bits=3)

    def scores_in_bf16(x, kernel):
        return bf16(jax.nn.sigmoid(bf16(bf16(x) @ bf16(kernel))))

    def matmul_in_float8(x, w):
        return float8(x) @ float8(w)

    def matmul_bf16_partial_sums(x, w):
        x, w = bf16(x), bf16(w)
        total = jnp.zeros(x.shape[:-1] + w.shape[-1:], jnp.float32)
        for lo in range(0, x.shape[-1], tile):
            total = bf16(total + x[..., lo:lo + tile] @ w[lo:lo + tile])
        return total

    return {"bf16": bf16, "scores_in_bf16": scores_in_bf16,
            "matmul_in_float8": matmul_in_float8,
            "matmul_bf16_partial_sums": matmul_bf16_partial_sums}


def controls(model, cfg, tile=TILE):
    """``{name: system}`` for ``checks_joyai.compare(system=...)``."""
    from ml_recipe_tpu.models import mla_moe
    from perfbench.harness import checks_joyai, reference_joyai

    program = checks_joyai.program(model)
    lowered = lowered_arithmetic(tile)

    def reference(p, inputs):
        preds, own = reference_joyai.forward(p, cfg, **inputs)
        return preds, own["chosen"], own["router_input"]

    return {
        "bf16_router": patched(mla_moe, "_router_scores",
                               lowered["scores_in_bf16"], program),
        "float8_matmuls": patched(reference_joyai, "_matmul",
                                  lowered["matmul_in_float8"], reference),
        "bf16_partial_sums": patched(
            reference_joyai, "_matmul", lowered["matmul_bf16_partial_sums"],
            reference),
    }


def grouped_matmul_rounding(params, states, chosen, preset):
    """The first expert layer's grouped matmul on the rows its routing
    dispatched: the bf16 result the program asks for against the float32
    result rounded to bf16 once. Equal everywhere means the sum over the
    contracted axis is kept in f32 and rounded at the end."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.ops import expert_ffn
    from ml_recipe_tpu.ops.grouped_matmul import grouped_matmul

    experts = params["transformer"]["layer_1"]["mlp"]["experts"]
    w = jnp.concatenate([experts["gate"], experts["up"]], -1).astype(
        jnp.bfloat16)

    @jax.jit
    def both(x, chosen):
        tokens = x.reshape(-1, x.shape[-1]).astype(jnp.bfloat16)
        picks = chosen.reshape(tokens.shape[0], -1)
        plan = expert_ffn.make_plan(
            picks, jnp.ones(picks.shape, jnp.float32), preset.experts_first,
            preset.experts_held, preset.n_routed_experts)
        chunk = expert_ffn._chunk_of(plan, 0, plan.capacity)
        rows = expert_ffn._dispatch(tokens, chunk)
        return (chunk.valid,
                grouped_matmul(rows, w, chunk.sizes,
                               expert_ffn._rows_an_expert(plan)),
                jax.lax.ragged_dot(rows, w, chunk.sizes,
                                   preferred_element_type=jnp.float32))

    # compared on the host: on the chip XLA may skip a rounding it is asked
    # for (excess precision) and compare the unrounded values
    live, asked, exact = (np.asarray(a) for a in jax.device_get(
        both(states[0], chosen[0])))
    asked, exact = asked[live], exact[live]
    once = exact.astype(asked.dtype)
    gap = np.abs(asked.astype(np.float32) - exact)
    return {"elements_that_differ": int((asked != once).sum()),
            "of": int(asked.size), "largest_gap": float(gap.max()),
            "largest_gap_of_one_rounding": float(
                np.abs(once.astype(np.float32) - exact).max())}


def load_without_the_common_component(params, states, mask, cfg):
    """Fullest held expert over the mean of the held, per expert layer: as
    routed, and routed on states less their mean over the real tokens (what
    every token shares after the norm)."""
    import jax
    import numpy as np

    from perfbench.harness import checks_joyai, reference_joyai

    held = cfg["experts_held"]
    real = np.asarray(mask, bool)
    route = jax.jit(lambda p, x: reference_joyai.route(p, cfg, x)[0])

    def skew(chosen):
        counts = np.bincount(np.asarray(chosen)[real].ravel(),
                             minlength=held["of"])
        mine = counts[held["first"]:held["first"] + held["count"]]
        return {"held_max_over_mean": float(mine.max() / mine.mean()),
                "all_max_over_mean": float(counts.max() / counts.mean()),
                "held_share": float(mine.sum() / counts.sum())}

    out = []
    for (_, mlp), x in zip(checks_joyai.expert_layers(params, "router"),
                           states):
        x = np.asarray(jax.device_get(x), np.float32)
        mean = x[real].mean(0)
        out.append({
            "as_routed": skew(route(mlp["router"], x)),
            "common_component_removed": skew(route(mlp["router"], x - mean)),
            "common_component_norm_over_state_norm": float(
                np.linalg.norm(mean)
                / np.sqrt((x[real] ** 2).sum(-1).mean())),
        })
    return out


def arguments(doc, argv):
    parser = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, nargs="+", default=[2700000301])
    parser.add_argument("--controls", nargs="*", default=None,
                        help="which of the controls to run (all by default)")
    parser.add_argument("--rehearse", action="store_true",
                        help="tests only: the cell's tiny size, any backend")
    return parser.parse_args(argv)


def verdicts_by_seed(args, cell_name, compare, controls):
    """Per seed ``(seed, {system or control: compare's verdict}, trainer,
    cfg, flags)``: seeded weights of the cell's preset (its tiny one under
    ``--rehearse``) judged by ``compare`` as the benchmark would, then with
    each of ``controls(model, cfg, tile)`` in the system's place."""
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import build_mesh
    from perfbench.harness.manifest import load_cell

    cell = load_cell(cell_name)
    job = cell.traffic["rehearsal"] if args.rehearse else {}
    cfg = job["reference_config"] if args.rehearse else cell.config
    preset = MODEL_PRESETS[job["model"] if args.rehearse else cfg["model"]]
    flags = types.SimpleNamespace(
        max_seq_len=(job or cell.traffic["job"])["flags"]["max_seq_len"],
        loss="smooth", smooth_alpha=0.01)
    model = QAModel(preset, dtype=jnp.bfloat16, attention_impl="auto")
    lowered = controls(model, cfg, 8 if args.rehearse else TILE)
    names = list(lowered) if args.controls is None else args.controls
    init = jax.jit(lambda key: QAModel(preset, attention_impl="xla").init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])
    for seed in args.seeds:
        trainer = types.SimpleNamespace(
            model=model, loss=build_loss(flags), mesh=build_mesh("data:1"),
            params=init(jax.random.key(seed)))
        yield seed, {
            name: compare(trainer, cell, job, flags, seed, True,
                          system=system)
            for name, system in [("system", None)] + [
                (n, lowered[n]) for n in names]}, trainer, cfg, flags


def main(argv=None) -> int:
    import jax

    from perfbench.harness import checks, checks_joyai

    args = arguments(__doc__, argv)
    for seed, verdicts, trainer, cfg, flags in verdicts_by_seed(
            args, "joyai-ep16-train-seq4096", checks_joyai.compare, controls):
        seq = int(flags.max_seq_len)
        inputs, _ = checks.seeded_rows(
            seed, cfg["vocab_size"], seq,
            [seq, (3 * seq) // 4, (2 * seq) // 5, max(8, seq // 7)])
        _, chosen, states = jax.jit(checks_joyai.program(trainer.model))(
            trainer.params, inputs)
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "verdicts": verdicts,
            "grouped_matmul_bf16_result_against_f32_rounded_once":
                grouped_matmul_rounding(trainer.params, states, chosen,
                                        trainer.model.cfg),
            "held_load": load_without_the_common_component(
                trainer.params, states, inputs["attention_mask"], cfg),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

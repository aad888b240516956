"""The readings behind ``ops/expert_ffn.py``'s chunk sizes, on the chip at the
published widths (PERF.md section 6, PR 32, records them). Per MoE cell and
seed, on seeded weights of the cell's preset and full rows of ids uniform
over its vocabulary (the cell's traffic), for every expert layer and each of
a step's micro-batches: the assignments held over the assignments expected
(``T x K x held / all``), beside the first chunk and the granule that
``expert_ffn.chunk_sizes`` gives that micro-batch, and what the layer's own
counters read there (``moe_overflow_chunks``, ``moe_filler_share``,
``moe_row_tile_fill``).

The first chunk's margin has to lie above nearly every reading, of the cell
with the skewed router too: a trip round the granules' loop costs most of a
layer whatever its rows, far more than the filler a tighter margin saves.

    chiprun -- python scripts/moe_held_readings.py --seeds 1 2 3 4 5 6
"""

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELLS = ("lfm2-ep4-train-seq8192", "joyai-ep16-train-seq4096")
COUNTERS = ("moe_held_assignments", "moe_overflow_chunks", "moe_filler_share",
            "moe_row_tile_fill")


def readings(cell_name: str, seeds, split: int, rehearse: bool):
    """Per seed ``(seed, sizes, counters)``: ``chunk_sizes`` of a micro-batch
    beside its expectation, and ``counters[micro_batch][name][layer]`` as
    ``expert_ffn.routing_stats`` read them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.models.mla_moe import ROUTING
    from ml_recipe_tpu.ops.expert_ffn import chunk_sizes
    from perfbench.harness.checks_joyai import expert_layers
    from perfbench.harness.manifest import load_cell

    cell = load_cell(cell_name)
    job = cell.traffic["rehearsal" if rehearse else "job"]
    flags = job["flags"]
    cfg = MODEL_PRESETS[job.get("model", cell.config["model"])]
    seq = int(flags["max_seq_len"])
    rows = int(flags["train_batch_size"]) // split
    held = (cfg.num_experts_per_tok, cfg.experts_held, cfg.n_routed_experts)
    expected = rows * seq * held[0] * held[1] / held[2]
    capacity, granule = chunk_sizes(rows * seq, *held)
    sizes = {"tokens": rows * seq, "expected": expected,
             "first_chunk": capacity, "granule": granule}
    model = QAModel(
        cfg, dtype=jnp.float32 if rehearse else jnp.bfloat16,
        attention_impl="xla" if rehearse else "auto")
    init = jax.jit(lambda key: QAModel(cfg, attention_impl="xla").init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])

    @jax.jit
    def counters_a_layer(params, ids):
        _, sown = model.apply(
            {"params": params}, input_ids=ids,
            attention_mask=jnp.ones_like(ids), deterministic=True,
            mutable=[ROUTING])
        stats = [m["stats"][0] for _, m in expert_layers(sown[ROUTING])]
        return {name: jnp.stack([s[name] for s in stats])
                for name in COUNTERS}

    for seed in seeds:
        params = init(jax.random.key(seed))
        ids = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (split, rows, seq), dtype=np.int32)
        yield seed, sizes, [
            {name: [float(v) for v in values] for name, values in
             counters_a_layer(params, micro).items()} for micro in ids]


def summary(counters, expected: float) -> dict:
    """Of all (seed, micro-batch, layer) instances: the quartiles and ends
    of assignments held over ``expected``, and what the chunk sizes make the
    instances pay."""
    flat = {name: [v for per_seed in counters for micro in per_seed
                   for v in micro[name]] for name in COUNTERS}
    ratios = sorted(n / expected for n in flat["moe_held_assignments"])
    q1, q2, q3 = statistics.quantiles(ratios, n=4)
    return {
        "instances": len(ratios), "min": ratios[0], "q1": q1, "median": q2,
        "q3": q3, "max": ratios[-1],
        "over_the_first_chunk": sum(
            g > 0 for g in flat["moe_overflow_chunks"]),
        "granules": sum(flat["moe_overflow_chunks"]),
        "filler_share_mean": statistics.fmean(flat["moe_filler_share"]),
        "row_tile_fill_mean": statistics.fmean(flat["moe_row_tile_fill"]),
    }


def main(argv=None) -> int:
    import jax

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS))
    parser.add_argument("--seeds", type=int, nargs="+", default=[3200000001])
    parser.add_argument("--split", type=int, default=4,
                        help="micro-batches a step (the chip settles on 4 "
                             "in both cells)")
    parser.add_argument("--rehearse", action="store_true",
                        help="tests only: the cells' tiny size, any backend")
    args = parser.parse_args(argv)
    for cell in args.cells:
        counters = []
        for seed, sizes, per_seed in readings(
                cell, args.seeds, args.split, args.rehearse):
            counters.append(per_seed)
            print(json.dumps({
                "cell": cell, "seed": seed, "held_over_expected": [
                    [round(n / sizes["expected"], 4)
                     for n in micro["moe_held_assignments"]]
                    for micro in per_seed]}), flush=True)
        print(json.dumps({
            "cell": cell, "device": jax.devices()[0].device_kind,
            "split": args.split, **sizes,
            **summary(counters, sizes["expected"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""How ``fixtures/mellum2_tiny.xplane.pb`` and ``mellum2_tiny.scope_map.json``
were recorded on the chip (PR 37): three calls of a small ``train_step``
(value and gradient of the QA logits of a two-layer ``mellum`` trunk: a
sliding-window attention layer (window 200) and a full-attention layer under
YaRN, 4 query heads over 2 key/value heads of 64, each with an expert layer
holding 4 of 8 experts behind a softmax router, at B 2, L 768: three blocks of
256, so that the window kernels walk 5 of the triangle's 6 block pairs, the
causal kernels and the TPU's grouped-matmul kernels all run) under the
benchmark's own profiler settings, then the program's scope map as the trace
readers would ask for it. Run it on a TPU:

    python perfbench/fixtures/record_fixture_mellum2.py <out_dir>
"""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_recipe_tpu.metrics import trace as program_trace
    from ml_recipe_tpu.models import QAModel
    from ml_recipe_tpu.models.config import DecoderConfig
    from ml_recipe_tpu.utils.platform import configure_compile_cache
    from perfbench.harness import profiler

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record the fixture on a TPU")
    configure_compile_cache()       # whole scope paths in op_name
    cfg = DecoderConfig(
        model_type="mellum", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, layer_types=("sliding_attention", "full_attention"),
        num_kv_heads=2, head_dim=64, sliding_window=200, qk_norm=True,
        rope_interleaved=False, rope_theta=500000.0, yarn_factor=16.0,
        yarn_original_positions=256, yarn_attention_factor=1.2772588722239782,
        first_k_dense_replace=0, moe_intermediate_size=128,
        n_routed_experts=8, experts_first=2, experts_held=4,
        num_experts_per_tok=2, n_shared_experts=0, routed_scaling_factor=1.0,
        norm_topk_eps=0.0, scoring_func="softmax")
    model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="pallas")
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 768)), jnp.int32)
    params = QAModel(cfg, attention_impl="xla").init(
        jax.random.key(0), ids[:, :8])["params"]

    def loss(p, ids):
        with jax.named_scope("forward_backward"):
            out = model.apply({"params": p}, ids)
            return jnp.sum(out["cls"]) + jnp.sum(out["start_reg"])

    @jax.jit
    def train_step(p, ids):
        return jax.value_and_grad(loss)(p, ids)

    compiled = train_step.lower(params, ids).compile()
    program_trace.register_program("jit_train_step", compiled.as_text)
    jax.block_until_ready(train_step(params, ids))
    out_dir = Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = ROOT / "perfbench" / ".cache" / "trace" / "fixture_mellum2"
    profiler.start(trace_dir)
    for _ in range(3):
        jax.block_until_ready(train_step(params, ids))
    shutil.copy(profiler.stop(trace_dir), out_dir / "mellum2_tiny.xplane.pb")
    (out_dir / "mellum2_tiny.scope_map.json").write_text(json.dumps(
        {"jit_train_step": program_trace.scope_map("jit_train_step")},
        indent=0, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

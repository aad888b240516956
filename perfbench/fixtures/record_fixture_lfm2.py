"""How ``fixtures/lfm2_tiny.xplane.pb`` and ``lfm2_tiny.scope_map.json`` were
recorded on the chip (PR 31): three calls of a small ``train_step`` (value and
gradient of the QA logits of a two-layer ``lfm2_moe`` trunk: a convolution
layer with the dense FFN, then a grouped-query attention layer (4 query heads
over 2 key/value heads of 64) with an expert layer holding 4 of 8 experts and
no shared expert, at B 2, L 256, so that the causal kernels, the TPU's
grouped-matmul kernels and the ``short_conv`` fusions all run) under the benchmark's own profiler settings, then the
program's scope map as the trace readers would ask for it. Run it on a TPU:

    python perfbench/fixtures/record_fixture_lfm2.py <out_dir>
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
        model_type="lfm2_moe", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, layer_types=("conv", "full_attention"),
        num_kv_heads=2, head_dim=64, qk_norm=True, rope_interleaved=False,
        intermediate_size=256, moe_intermediate_size=128, n_routed_experts=8,
        experts_first=2, experts_held=4, num_experts_per_tok=2,
        n_shared_experts=0, routed_scaling_factor=1.0, norm_topk_eps=1e-6,
        rope_theta=1000000.0, rms_norm_eps=1e-5)
    model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="pallas")
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 256)), jnp.int32)
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
    trace_dir = ROOT / "perfbench" / ".cache" / "trace" / "fixture_lfm2"
    profiler.start(trace_dir)
    for _ in range(3):
        jax.block_until_ready(train_step(params, ids))
    shutil.copy(profiler.stop(trace_dir), out_dir / "lfm2_tiny.xplane.pb")
    (out_dir / "lfm2_tiny.scope_map.json").write_text(json.dumps(
        {"jit_train_step": program_trace.scope_map("jit_train_step")},
        indent=0, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

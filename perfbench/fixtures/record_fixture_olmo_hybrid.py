"""How ``fixtures/olmo_hybrid_tiny.xplane.pb`` and
``olmo_hybrid_tiny.scope_map.json`` were recorded on the chip (PR 33): three
calls of a small ``train_step`` (value and gradient of the QA logits of a
two-layer ``olmo_hybrid`` trunk: a gated delta-rule layer (2 heads, ``d_k``
64, ``d_v`` 128), then a full-attention layer (2 heads of 128, whole-width
q/k norm, no rotation), reordered norms, dense FFNs, ``remat`` on, at B 2,
L 256, so that the chunked scan forward, again and backward, its
convolutions, its gated norm and the causal kernels all run) under the
benchmark's own profiler settings, then the program's scope map as the trace
readers would ask for it. Run it on a TPU:

    python perfbench/fixtures/record_fixture_olmo_hybrid.py <out_dir>
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
        model_type="olmo_hybrid", vocab_size=512, hidden_size=256,
        num_layers=2, num_heads=2, intermediate_size=512,
        first_k_dense_replace=2,
        layer_types=("linear_attention", "full_attention"),
        qk_norm="whole", rope_theta=None, norm_after=True,
        linear_num_heads=2, linear_key_head_dim=64,
        linear_value_head_dim=128, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True)
    model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="pallas",
                    remat=True)
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
    trace_dir = ROOT / "perfbench" / ".cache" / "trace" / "fixture_olmo_hybrid"
    profiler.start(trace_dir)
    for _ in range(3):
        jax.block_until_ready(train_step(params, ids))
    shutil.copy(profiler.stop(trace_dir),
                out_dir / "olmo_hybrid_tiny.xplane.pb")
    (out_dir / "olmo_hybrid_tiny.scope_map.json").write_text(json.dumps(
        {"jit_train_step": program_trace.scope_map("jit_train_step")},
        indent=0, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

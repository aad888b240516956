"""How ``fixtures/tiny.xplane.pb`` was recorded on the chip (PR 22): three
calls of a small jitted program (two matmuls round the repo's Pallas
attention at B 2, L 128, H 2, D 64) under the benchmark's own profiler
settings and window markers, with a 20 ms ``bench:loader_next`` pause before
each call so that the idle gaps have a name. Run it on a TPU:

    python perfbench/fixtures/record_fixture.py <out.xplane.pb>
"""

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.ops.attention import dot_product_attention
    from perfbench.harness import profiler

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record the fixture on a TPU")
    B, L, H, D = 2, 128, 2, 64
    w = jnp.ones((H * D, H * D), jnp.bfloat16) / (H * D)
    mask = jnp.ones((B, L), jnp.int32)

    @jax.jit
    def tiny_step(x):
        q = (x @ w).reshape(B, L, H, D)
        ctx = dot_product_attention(q, q, q, mask, dtype=jnp.bfloat16,
                                    impl="pallas")
        return ctx.reshape(B, L, H * D) @ w

    x = jnp.ones((B, L, H * D), jnp.bfloat16)
    jax.block_until_ready(tiny_step(x))
    trace_dir = ROOT / "perfbench" / ".cache" / "trace" / "fixture"
    profiler.start(trace_dir)
    for _ in range(3):
        with profiler.annotation("bench:loader_next"):
            time.sleep(0.02)
        x = tiny_step(x)
        jax.block_until_ready(x)
    shutil.copy(profiler.stop(trace_dir), out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

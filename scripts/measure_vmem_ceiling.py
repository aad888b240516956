"""Measure the real scoped-VMEM ceiling of the attached TPU by bisection.

The flash-attention cfgs budget block+temp bytes against a constant; this
script replaces the folklore number with a measurement (VERDICT r3 #3): it
AOT-compiles a trivial Pallas kernel whose VMEM footprint is one f32 scratch
block of S bytes (plus an (8,128) in/out tile), and bisects the largest S
that Mosaic accepts. The verdict is the compiler's, so no chip is needed:
with a TPU attached it compiles for that chip, otherwise for a described
``v5e:2x2`` topology (compile-only):

    python scripts/measure_vmem_ceiling.py

Prints one JSON line {"vmem_ceiling_bytes": N, "device_kind": ...}. Put the
number in ``_SCOPED_VMEM_CEILING`` in ml_recipe_tpu/ops/flash_attention.py
under that device kind, with the jax/libtpu versions it was taken under.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the SAME overflow classifier the budget's consumer uses — the measured
# ceiling must be defined by the same predicate that probes against it
from ml_recipe_tpu.ops.flash_attention import _looks_like_vmem_overflow


def _kernel(x_ref, o_ref, scratch):
    scratch[0, :] = x_ref[0, :] * 2.0
    o_ref[...] = x_ref[...] + scratch[0, 0]


def _target():
    """(device the probe compiles for, how): the attached chip, else the
    first device of a described v5e:2x2."""
    if jax.default_backend() == "tpu":
        return jax.devices()[0], "attached"
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return topo.devices[0], "compile-only"


def compiles_with_scratch(scratch_bytes: int, device) -> bool:
    rows = max(8, scratch_bytes // (128 * 4))
    call = pl.pallas_call(
        _kernel,
        in_specs=[pl.BlockSpec((8, 128), lambda: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32)],
    )
    try:
        jax.jit(call).lower(
            jax.ShapeDtypeStruct(
                (8, 128), jnp.float32,
                sharding=jax.sharding.SingleDeviceSharding(device))
        ).compile()
        return True
    except Exception as e:  # noqa: BLE001
        if _looks_like_vmem_overflow(e):
            return False
        raise


def main() -> int:
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the way
    jax.config.update("jax_enable_compilation_cache", False)
    device, mode = _target()
    lo, hi = 1 << 20, 1 << 28  # 1 MB (must fit) .. 256 MB (must not)
    assert compiles_with_scratch(lo, device), "even 1 MB scratch failed"
    assert not compiles_with_scratch(hi, device), "256 MB scratch compiled?!"
    while hi - lo > 1 << 18:  # 256 KB resolution
        mid = (lo + hi) // 2
        if compiles_with_scratch(mid, device):
            lo = mid
        else:
            hi = mid
    print(json.dumps({
        "vmem_ceiling_bytes": lo,
        "vmem_ceiling_mib": round(lo / (1 << 20), 2),
        "resolution_bytes": 1 << 18,
        "device_kind": device.device_kind,
        "mode": mode,
        "jax": jax.__version__,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

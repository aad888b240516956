"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

This emulates a multi-chip TPU topology on the CPU host so sharding /
collective code paths are exercised without hardware (SURVEY.md §4).
"""

import os

# No-network environment: make HF hub fallbacks fail fast instead of
# retrying DNS for minutes (test_init_tokenizer_missing_vocab_raises
# measured 191s without this, <1s with it).
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

# Geometry-autotuner tuning cache (ops/autotune.py): point it at a per-run
# temp dir so tests — and the bench.py subprocess smokes, which inherit the
# env — never write into the repo's artifacts/tuning/.
if "MLRT_AUTOTUNE_CACHE" not in os.environ:
    import tempfile

    os.environ["MLRT_AUTOTUNE_CACHE"] = tempfile.mkdtemp(
        prefix="mlrt_tuning_cache_"
    )

# Force (not setdefault: the environment may name another platform) the CPU
# platform with 8 virtual devices for every test run; subprocesses inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# a persistent-cache hit on the CPU backend logs an E-level pseudo-feature
# mismatch (+prefer-no-scatter/+prefer-no-gather are XLA-internal, absent from
# the host prober's list). Level 2 keeps real native ERRORs visible (level 3
# would also hide genuine XLA failures in every inherited subprocess).
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# JAX's persistent compilation cache, placed by the same function every entry
# point calls (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache):
# the subprocess smokes and multiprocess worlds run the entry points, so they
# land in the same directory and re-use this process's compiles.
from ml_recipe_tpu.utils.platform import configure_compile_cache  # noqa: E402

configure_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices

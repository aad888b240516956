"""Parallel-layer tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ml_recipe_tpu.parallel import (
    MeshSpec,
    barrier,
    batch_pspec,
    build_mesh,
    is_primary,
    make_global_array,
    param_pspecs,
    pmean,
    shard_params,
)


def test_mesh_spec_parsing():
    spec = MeshSpec.from_string("data:4,model:2")
    assert spec.size == 8
    assert list(spec.ordered().keys()) == ["data", "model"]
    default = MeshSpec.from_string(None, n_devices=8)
    assert default.axes == {"data": 8}


def test_build_mesh_default(eight_devices):
    mesh = build_mesh()
    assert mesh.shape == {"data": 8}


def test_build_mesh_2d(eight_devices):
    mesh = build_mesh("data:4,model:2")
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2


def test_build_mesh_too_large(eight_devices):
    with pytest.raises(ValueError):
        build_mesh("data:16")


def test_build_mesh_subset(eight_devices):
    # smaller specs take the first N devices (single-chip eval on a pod host)
    mesh = build_mesh("data:2")
    assert mesh.shape == {"data": 2}


def test_param_pspecs_tp(eight_devices):
    mesh = build_mesh("data:4,model:2")
    params = {
        "layer_0": {
            "attention": {
                "query": {"kernel": np.zeros((8, 8)), "bias": np.zeros(8)},
                "output": {"kernel": np.zeros((8, 8)), "bias": np.zeros(8)},
            },
            "mlp": {
                "intermediate": {"kernel": np.zeros((8, 16)), "bias": np.zeros(16)},
                "output": {"kernel": np.zeros((16, 8)), "bias": np.zeros(8)},
            },
        },
        "pooler": {"kernel": np.zeros((8, 8)), "bias": np.zeros(8)},
    }
    specs = param_pspecs(params, mesh)
    att = specs["layer_0"]["attention"]
    assert att["query"]["kernel"] == P(None, "model")
    assert att["output"]["kernel"] == P("model", None)
    assert specs["layer_0"]["mlp"]["intermediate"]["kernel"] == P(None, "model")
    assert specs["pooler"]["kernel"] == P()  # replicated

    sharded = shard_params(params, mesh, specs)
    q = sharded["layer_0"]["attention"]["query"]["kernel"]
    assert q.sharding.spec == P(None, "model")


def test_param_pspecs_data_only(eight_devices):
    mesh = build_mesh("data:8")
    params = {"attention": {"query": {"kernel": np.zeros((4, 4))}}}
    specs = param_pspecs(params, mesh)
    assert specs["attention"]["query"]["kernel"] == P()


def test_batch_pspec(eight_devices):
    mesh = build_mesh("data:2,seq:4")
    assert batch_pspec(mesh, ndim=2) == P("data", None)
    assert batch_pspec(mesh, shard_seq=True, ndim=2) == P("data", "seq")
    assert batch_pspec(mesh, ndim=1) == P("data")


def test_make_global_array(eight_devices):
    mesh = build_mesh("data:8")
    batch = {"input_ids": np.arange(64).reshape(8, 8), "cls": np.arange(8)}
    garr = make_global_array(batch, mesh)
    assert garr["input_ids"].shape == (8, 8)
    assert garr["input_ids"].sharding.spec == P("data", None)
    np.testing.assert_array_equal(np.asarray(garr["input_ids"]), batch["input_ids"])


def test_pmean_matches_ddp_mean(eight_devices):
    """Gradient pmean over the data axis == DDP's world-mean contract."""
    from ml_recipe_tpu.parallel.compat import shard_map

    mesh = build_mesh("data:8")

    @jax.jit
    def f(x):
        return shard_map(
            lambda v: pmean(v, "data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )(x)

    x = jnp.arange(8.0)
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.5))


def test_single_process_helpers():
    assert is_primary() is True
    barrier("noop")  # single-process no-op must not hang


def test_local_cover_shards_rejects_overlap():
    """Volume-sum coverage must not accept overlapping-but-unequal shard
    ranges — they double-count and would leave np.empty garbage in regions
    no shard wrote (advisor r3). Not producible with this repo's
    NamedShardings; pinned against a stub since the helper is generic."""
    from ml_recipe_tpu.parallel.sharding import _local_cover_shards

    class _Shard:
        def __init__(self, index, data):
            self.index = index
            self.data = data

    # volumes SUM to the total (3*2 + 1*2 = 8) but ranges overlap in rows
    # [1:2) and rows [3:4) are never written — the pre-fix volume-sum check
    # reported full coverage here
    class _Adversarial:
        shape = (4, 2)
        dtype = np.float32
        addressable_shards = [
            _Shard((slice(0, 3), slice(0, 2)), np.zeros((3, 2))),
            _Shard((slice(1, 2), slice(0, 2)), np.zeros((1, 2))),
        ]

    assert _local_cover_shards(_Adversarial()) is None


def test_local_cover_shards_accepts_disjoint_and_replicated(eight_devices):
    """Real NamedShardings still pass: disjoint row shards and fully
    replicated arrays both cover."""
    from ml_recipe_tpu.parallel.sharding import _local_cover_shards

    mesh = build_mesh("data:8")
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    sharded = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    replicated = jax.device_put(x, NamedSharding(mesh, P(None, None)))
    assert _local_cover_shards(sharded) is not None
    assert _local_cover_shards(replicated) is not None

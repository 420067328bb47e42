"""``repro.dist.mesh``: every mesh has Auto axes, so the models'
sharding constraints apply on it, and a layout that does not match the
devices is refused rather than degraded."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding

from repro.configs import get_config
from repro.dist.mesh import (make_abstract_production_mesh, make_device_mesh,
                             make_host_mesh)
from repro.dist.sharding import logical_to_spec
from repro.models.layers import Ctx
from repro.models.params import param_shardings


def test_host_mesh_constrain_runs_under_jit():
    mesh = make_host_mesh()
    assert mesh.devices.shape == (1, 1)
    assert mesh.axis_names == ("data", "model")
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    ctx = Ctx(mesh=mesh, dtype=jnp.float32)
    x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
    y = jax.jit(lambda a: ctx.constrain(a, ("batch", "embed_act")) * 2)(x)
    np.testing.assert_array_equal(np.asarray(y), 2 * np.asarray(x))


@pytest.mark.parametrize("multi_pod", [False, True])
def test_abstract_production_mesh_prices_shards(multi_pod):
    mesh = make_abstract_production_mesh(multi_pod=multi_pod)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    assert sizes == ({"pod": 2, "data": 16, "model": 16} if multi_pod
                     else {"data": 16, "model": 16})
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    # batch over (pod, data), vocab over model, embed replicated
    spec = logical_to_spec(("batch", "seq", "vocab_act"), (64, 128, 4096),
                           mesh)
    batch_axes = ("pod", "data") if multi_pod else "data"
    assert tuple(spec) == (batch_axes, None, "model")
    assert NamedSharding(mesh, spec).shard_shape((64, 128, 4096)) == \
        (64 // (32 if multi_pod else 16), 128, 4096 // 16)
    # the whole parameter tree: the embedding table is vocab x embed
    cfg = get_config("qwen3-0.6b")
    emb = param_shardings(cfg, mesh)["embed"]
    V, D = cfg.padded_vocab, cfg.d_model
    assert emb.shard_shape((V, D)) == (V // 16, D // 16)


def test_device_mesh_matches_devices_or_raises():
    one = jax.devices()[:1]
    mesh = make_device_mesh(devices=one)
    assert mesh.devices.shape == (1, 1, 1)
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    with pytest.raises(ValueError, match="does not match"):
        make_device_mesh(data=2, devices=one)
    with pytest.raises(ValueError, match="does not match"):
        make_device_mesh(tensor=2, devices=one)


def test_host_mesh_refuses_more_devices_than_present():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(data=n + 1)

"""Device-mesh construction.  Functions, not module constants — importing
this module never touches jax device state.

Two mesh vocabularies are in play and ``repro.dist.sharding``'s rule
table lists alternatives for both (absent axis names auto-drop):

* the fixed production pod meshes, axes ``("pod", "data", "model")`` —
  what the dry-run compiles against;
* generic ``("data", "fsdp", "tensor")`` meshes sized to whatever
  devices exist — what a learner pod builds at startup.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` (``models/layers.py``), which only accepts
Auto axes.  ``jax.make_mesh`` would otherwise build Explicit ones.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType, Mesh


def _auto(n: int):
    return (AxisType.Auto,) * n


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis is pure
    data parallelism (cross-pod traffic = one gradient all-reduce/step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_abstract_production_mesh(*, multi_pod: bool = False):
    """AbstractMesh twin of :func:`make_production_mesh` — carries only axis
    names/sizes, so placement analytics (``launch.specs.placement_report``)
    can price the 256/512-chip meshes on a single-CPU test host.
    ``NamedSharding.shard_shape`` works on it; compiling does not."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """(data, model) mesh over the first ``data × model`` local devices —
    one device by default (CPU smoke tests, one-chip serving), the
    production axis names so the same rules apply."""
    n = data * model
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"mesh data={data} x model={model} needs {n} "
                         f"devices; {len(devices)} present")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2), devices=devices[:n])


def make_device_mesh(
    *,
    data: Optional[int] = None,
    fsdp: int = 1,
    tensor: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """(data, fsdp, tensor) mesh over the available devices.

    ``data=None`` absorbs whatever devices remain after fsdp × tensor.
    A request whose ``data × fsdp × tensor`` is not the device count
    raises: a learner never silently runs with another layout.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        data = n // (fsdp * tensor)
    if data < 1 or data * fsdp * tensor != n:
        raise ValueError(
            f"mesh data={data} x fsdp={fsdp} x tensor={tensor} does not "
            f"match the {n} device(s) given")
    arr = np.asarray(devices).reshape(data, fsdp, tensor)
    return Mesh(arr, ("data", "fsdp", "tensor"), axis_types=_auto(3))

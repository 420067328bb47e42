"""Logical-axis sharding: one rule table maps model-space axis names to
mesh axes; everything else (specs, NamedShardings, per-shard memory) is
derived mechanically from it.

Model code never mentions mesh axes.  Parameters and activations carry
*logical* axis names (``"embed"``, ``"heads"``, ``"batch"`` …); the rule
table decides which mesh axes each logical axis shards over.  Three
well-formedness guarantees are enforced at spec-construction time:

* **auto-drop (absent)**    — a rule may name mesh axes that the current
  mesh does not have (``"pod"`` on a single-pod mesh, ``"fsdp"`` on the
  2-axis production mesh).  Absent axes are silently skipped, so one
  table serves every mesh.
* **auto-drop (indivisible)** — a mesh axis whose size does not divide
  the dimension is skipped rather than producing an XLA error (e.g.
  ``kv_heads=2`` over ``model=16`` replicates instead of splitting
  ``head_dim``).
* **use-once**              — a mesh axis already consumed by an earlier
  dimension of the same spec is skipped (PartitionSpecs must not repeat
  mesh axes).

A dimension made of whole units names its axis as ``(name, unit)``: the
token-major page pool's row is ``K`` kv heads of ``head_dim`` values side
by side, ``("kv_heads", head_dim)``, so a mesh axis shards it only when
it divides the head count — never mid-head.

``DEFAULT_RULES`` is the production table; per-cell overrides (the §Perf
hillclimbing knob, e.g. sequence-parallel residuals) go through
:meth:`ShardingRules.override`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

#: a logical axis name, None (replicated), or (name, unit) — see above
AxisName = Union[None, str, Tuple[str, int]]
MeshAxes = Tuple[str, ...]


def _normalize(axes: Union[None, str, Sequence[str]]) -> MeshAxes:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


@dataclass(frozen=True)
class ShardingRules:
    """Immutable logical-axis → mesh-axes table.

    Stored as a tuple of pairs so rule sets are hashable (they ride on
    :class:`repro.models.layers.Ctx`, a frozen dataclass).  Mesh axes are
    tried in rule order; see the module docstring for the drop rules.
    """

    rules: Tuple[Tuple[str, MeshAxes], ...] = ()

    def as_dict(self) -> Dict[str, MeshAxes]:
        return dict(self.rules)

    def axes_for(self, logical: str) -> MeshAxes:
        table = self.as_dict()
        if logical not in table:
            raise KeyError(
                f"no sharding rule for logical axis {logical!r}; "
                f"known: {sorted(table)}")
        return table[logical]

    def override(self, **kw: Union[None, str, Sequence[str]]) -> "ShardingRules":
        """New table with the given logical axes remapped (or added).
        ``axis=()`` / ``axis=None`` replicates; ``axis="model"`` or
        ``axis=("model", "pod")`` shards."""
        table = self.as_dict()
        table.update({k: _normalize(v) for k, v in kw.items()})
        return ShardingRules(tuple(sorted(table.items())))


def _mesh_sizes(mesh) -> Dict[str, int]:
    # ``axis_sizes`` covers both concrete Mesh and AbstractMesh — the latter
    # lets placement analytics price a 256-chip mesh on a 1-CPU test host.
    if hasattr(mesh, "axis_sizes"):
        return dict(zip(mesh.axis_names, mesh.axis_sizes))
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ---------------------------------------------------------------------------
# The production rule table.
#
# Convention (documented in README §Sharding):
#   * ``*_act`` names are activation axes; bare names are parameter axes.
#   * Parameters: FSDP over the data-parallel axis on the "embed" dim,
#     tensor parallelism over the model axis on heads/ffn/vocab/experts.
#   * Activations: batch over data, embed replicated (gathered at the
#     norm), logits vocab-sharded, residual sequence replicated unless
#     the sequence-parallel override flips ``resid_seq`` on.
#   * Each rule lists alternatives for BOTH mesh vocabularies — the
#     production ("pod", "data", "model") meshes and the generic
#     ("data", "fsdp", "tensor") meshes of repro.dist.mesh — absent
#     names auto-drop.
# ---------------------------------------------------------------------------
DEFAULT_RULES = ShardingRules().override(
    # activation axes
    batch=("pod", "data"),
    cache_batch=("pod", "data"),
    seq=(),
    resid_seq=(),            # override to ("model",) for Megatron-SP residuals
    # KV-cache placement (README §Serving cache placement):
    #   * ``kv_seq``      — sequence dim of *global* position-indexed caches.
    #     Sharded over the tensor axis; when ``kv_heads`` already consumed it
    #     (divisible head count) the use-once rule drops it and the cache is
    #     head-sharded instead.  Either way the 32k decode cache stops being
    #     replicated over the model axis.
    #   * ``window_seq``  — slot dim of ring-buffer (sliding-window) caches.
    #     NEVER sharded: the ``pos % window`` scatter wraps around, so a
    #     sharded ring would scatter across devices every step.  Ring buffers
    #     are batch-sharded through ``cache_batch`` only.
    #   * ``cache_pages`` — physical-page dim of the paged pool.  Pages have
    #     no batch dim (the pool is shared), so they shard over batch-ish
    #     axes AND the tensor axis; the serving allocator keeps a sequence's
    #     pages inside its own data shard (launch.executor.PagePool partitions
    #     its free lists per shard — spec-level invariants are checked by
    #     check_cache_locality).
    kv_seq=("tensor", "model"),
    window_seq=(),
    cache_pages=("pod", "data", "tensor", "model"),
    embed_act=(),
    vocab_act=("tensor", "model"),
    # parameter axes
    embed=("fsdp", "data"),
    vocab=("tensor", "model"),
    heads=("tensor", "model"),
    kv_heads=("tensor", "model"),
    head_dim=(),
    ffn=("tensor", "model"),
    experts=("tensor", "model"),
    expert_ffn=(),
    capacity=(),
    rnn=("tensor", "model"),
    lora=(),
    conv=(),
    layers=(),               # the scan dim is never sharded
)


def logical_to_spec(
    logical_axes: Sequence[AxisName],
    shape: Sequence[int],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> PartitionSpec:
    """Map per-dimension logical axis names to a valid ``PartitionSpec``.

    ``None`` entries replicate that dimension; a ``(name, unit)`` entry
    shards by whole units of ``unit`` elements.  Unknown logical names
    raise ``KeyError`` (a typo must fail loudly, not silently replicate).
    Trailing replicated dims are trimmed from the spec.
    """
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    sizes = _mesh_sizes(mesh)
    used: set = set()
    entries: list = []
    for name, dim in zip(logical_axes, shape):
        if name is None:
            entries.append(None)
            continue
        if isinstance(name, tuple):
            name, unit = name
            dim //= unit
        chosen: list = []
        prod = 1
        for ax in rules.axes_for(name):
            if ax not in sizes or ax in used:
                continue
            if dim % (prod * sizes[ax]) != 0:
                continue
            chosen.append(ax)
            prod *= sizes[ax]
        used.update(chosen)
        if not chosen:
            entries.append(None)
        elif len(chosen) == 1:
            entries.append(chosen[0])
        else:
            entries.append(tuple(chosen))
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def make_named_sharding(
    logical_axes: Sequence[AxisName],
    shape: Sequence[int],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, shape, mesh, rules))


# ---------------------------------------------------------------------------
# Pytree-wide inference over abstract leaves.
#
# An "abstract leaf" is anything carrying ``.shape`` and ``.logical_axes``
# (repro.models.params.ParamAb and the abstract cache reuse of it) — the
# tree is evaluated without allocating a single array.
# ---------------------------------------------------------------------------
def is_abstract_leaf(x) -> bool:
    return hasattr(x, "logical_axes") and hasattr(x, "shape")


def tree_shardings(tree, mesh: Mesh, rules: ShardingRules = DEFAULT_RULES):
    """NamedSharding for every abstract leaf of ``tree``."""
    return jax.tree.map(
        lambda ab: make_named_sharding(ab.logical_axes, ab.shape, mesh, rules),
        tree, is_leaf=is_abstract_leaf)


def _shard_factor(spec: PartitionSpec, sizes: Dict[str, int]) -> int:
    f = 1
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            f *= sizes[ax]
    return f


def _spec_entries(spec: PartitionSpec, ndim: int) -> Tuple:
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    return entries


def check_cache_locality(tree, mesh, rules: ShardingRules = DEFAULT_RULES) -> Dict[str, PartitionSpec]:
    """Well-formedness of a KV-cache sharding: decode gather/scatter must
    stay shard-local.

    Enforced invariants, per abstract cache leaf:

    * ``window_seq`` dims are replicated — the ring buffer's ``pos % window``
      scatter wraps, so a sharded ring would cross shards every decode step;
    * unnamed (``None``) dims — per-slot position metadata, page tables'
      page-index dim, the within-page token dim of a page pool — are
      replicated: they are read in full every step.

    These are *spec-level* invariants.  Which physical page a sequence's
    table points at is runtime data, so page→shard locality is enforced by
    the serving allocator instead (``launch.executor.PagePool`` partitions its
    free lists per data shard).

    Returns ``{leaf_path: spec}`` for introspection; raises ``ValueError``
    on the first violation.
    """
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_abstract_leaf)
    out: Dict[str, PartitionSpec] = {}
    for path, ab in leaves:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = logical_to_spec(ab.logical_axes, ab.shape, mesh, rules)
        entries = _spec_entries(spec, len(ab.shape))
        for lax_name, entry in zip(ab.logical_axes, entries):
            axes = () if entry is None else (
                entry if isinstance(entry, tuple) else (entry,))
            if lax_name == "window_seq" and axes:
                raise ValueError(
                    f"cache leaf {name!r}: ring-buffer slot dim is sharded "
                    f"over {axes} — the pos%window scatter would cross "
                    f"shards every decode step; map 'window_seq' to ()")
            if lax_name is None and axes:
                raise ValueError(
                    f"cache leaf {name!r}: metadata dim sharded over {axes} "
                    f"— pos/page-table metadata must be replicated")
        out[name] = spec
    return out


def tree_shard_bytes(
    tree,
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
    dtype_override=None,
) -> int:
    """Analytic per-device bytes of the sharded tree (placement planning:
    no compile needed).  Divisibility is exact — auto-drop guarantees every
    kept mesh axis divides its dimension."""
    import jax.numpy as jnp

    sizes = _mesh_sizes(mesh)
    total = 0
    for ab in jax.tree.leaves(tree, is_leaf=is_abstract_leaf):
        spec = logical_to_spec(ab.logical_axes, ab.shape, mesh, rules)
        dt = jnp.dtype(dtype_override if dtype_override is not None
                       else getattr(ab, "dtype", "float32"))
        n = int(np.prod(ab.shape, dtype=np.int64)) if ab.shape else 1
        total += n * dt.itemsize // _shard_factor(spec, sizes)
    return total

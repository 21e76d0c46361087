"""Logical-axis → mesh-axis sharding rules (DP / FSDP / TP / EP / SP).

Models annotate every parameter dim with a logical name (see
``repro.models.param``); this module maps those names onto the physical
mesh. Two rule sets:

  * ``rules(fsdp=False)`` — tensor-parallel weights over "model", batch
    over ("pod","data"); parameters replicated across "data" (plain DP).
  * ``rules(fsdp=True)``  — additionally shards the "embed" dim of every
    weight over "data" (FSDP/ZeRO-3: params, grads *and* Adam moments all
    sharded 256/512-way; XLA inserts the all-gathers on use and
    reduce-scatters on the gradient side).

Elastic scaling: nothing below references absolute sizes — re-running
with a different mesh shape re-lowers the same program (restore from
checkpoint and continue on more or fewer pods).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["rules", "pspec", "named_sharding", "tree_shardings",
           "batch_pspec", "constrain", "axis_extent", "data_axis_extent"]


def rules(fsdp: bool = False, multi_pod: bool = True,
          conv_tp: bool = False) -> dict:
    data_axes = ("pod", "data") if multi_pod else ("data",)
    r = {
        "batch": data_axes,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        # expert weights are (E, d, f): EP shards the expert axis over
        # "model"; the per-expert hidden dim must then stay unsharded
        # (one mesh axis can map to only one tensor dim).
        "expert_mlp": None,
        "experts": "model",
        "embed": None,
        "layers": None,
        "seq": None,
        # Conv-serving logical axes (the int8 Winograd pipeline). "T" is
        # the flattened batch·tile axis of the Winograd domain — it is
        # batch-like, so it shards across the full DP extent (each device
        # runs the fused serving kernel on its tile slab). "cout" is the
        # conv tensor-parallel seam: the per-position GEMM's N axis,
        # sharded over "model" so one hot layer's packed weights can
        # outgrow a single device (``conv_tp=True``; the packed-state
        # placement only engages it when the serving engine asks — see
        # ``repro.conv.packing.packed_tree_shardings(model_axis=)``).
        "T": data_axes,
        "cout": "model" if conv_tp else None,
        "cin": None,
        "wino_pos": None,       # the n² Winograd positions — never sharded
        None: None,
    }
    if fsdp:
        # ZeRO-3: shard the d_model dim of weights across the full DP
        # extent — ("pod","data") jointly on multi-pod meshes, so params
        # + moments scale down with every added pod.
        r["embed"] = data_axes
    return r


def _axis_extent(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


# When a logical axis can't take its mesh axis (dim not divisible — e.g.
# qwen2-moe's 60 experts on a 16-way model axis), retry the freed mesh
# axis on another dim of the same tensor, in this priority order.
_RESHARD_RETRY = ("expert_mlp", "mlp", "heads", "kv_heads", "vocab",
                  "embed")


def pspec(axes: tuple, rule_map: dict, shape: tuple | None = None) -> P:
    """PartitionSpec for one tensor; divisibility-aware when shape given."""
    entries = [rule_map.get(a, None) for a in axes]
    if shape is None:
        return P(*entries)
    # drop mesh axes that don't divide their dim; remember them
    dropped = []
    mesh_shape = rule_map.get("__mesh_shape__", {})

    def extent(e):
        if e is None:
            return 1
        if isinstance(e, (tuple, list)):
            n = 1
            for a in e:
                n *= mesh_shape.get(a, 1)
            return n
        return mesh_shape.get(e, 1)

    for i, (e, d) in enumerate(zip(entries, shape)):
        if e is not None and d % extent(e) != 0:
            dropped.append(e)
            entries[i] = None
    # retry dropped axes on other dims (largest-benefit first: single axes)
    for e in dropped:
        if isinstance(e, (tuple, list)):
            continue
        for retry_name in _RESHARD_RETRY:
            placed = False
            for i, a in enumerate(axes):
                if a == retry_name and entries[i] is None and \
                        shape[i] % extent(e) == 0 and \
                        e not in [x for x in entries if x is not None]:
                    entries[i] = e
                    placed = True
                    break
            if placed:
                break
    return P(*entries)


def named_sharding(mesh: Mesh, axes: tuple, rule_map: dict,
                   shape: tuple | None = None) -> NamedSharding:
    rm = dict(rule_map)
    rm["__mesh_shape__"] = dict(mesh.shape)
    return NamedSharding(mesh, pspec(axes, rm, shape))


def tree_shardings(mesh: Mesh, axes_tree, rule_map: dict,
                   abstract_tree=None):
    """Pytree of NamedShardings congruent to a logical-axes pytree.

    ``abstract_tree`` (ShapeDtypeStructs) enables divisibility-aware specs
    with fallback placement — required because jit in_shardings reject
    non-divisible dims.
    """
    is_axes = lambda x: isinstance(x, tuple) and \
        all(isinstance(a, (str, type(None))) for a in x)
    if abstract_tree is None:
        return jax.tree.map(
            lambda axes: named_sharding(mesh, axes, rule_map), axes_tree,
            is_leaf=is_axes)
    return jax.tree.map(
        lambda axes, ab: named_sharding(mesh, axes, rule_map, ab.shape),
        axes_tree, abstract_tree, is_leaf=is_axes)


def batch_pspec(rule_map: dict) -> P:
    return P(rule_map["batch"])


def constrain(x, mesh: Mesh, *axes):
    """with_sharding_constraint by mesh axis names (None = replicated)."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*axes)))


def axis_extent(mesh: Mesh, name=None) -> int:
    """Device count along one mesh axis of a (possibly multi-axis) mesh.

    ``name`` is a mesh axis name, a tuple of names (product of extents —
    e.g. ``("pod", "data")`` on a multi-pod mesh), or ``None`` (extent
    1, the replicated case). Axes the mesh does not have extent 1 —
    the same 1-D mesh that serves data-only today reads as a degenerate
    2-D (D, 1) data×model mesh, so every caller can be written against
    the general shape.
    """
    if name is None:
        return 1
    names = name if isinstance(name, (tuple, list)) else (name,)
    shape = dict(mesh.shape)
    n = 1
    for a in names:
        n *= shape.get(a, 1)
    return n


def data_axis_extent(mesh: Mesh, axis="data") -> int:
    """Device count along ``axis``; legacy 1-D-era name for
    ``axis_extent`` (kept for callers of the tile-sharding API). Unlike
    the general form it raises on an axis the mesh does not have."""
    return _axis_extent(mesh, axis)


"""Logical-axis -> mesh-axis resolution (DP/FSDP/TP/EP/SP).

Parameters carry logical axis names (see ``models.base.P``); this module
maps them onto the production mesh:

    experts  -> "model"   (expert parallelism for MoE)
    heads / kv_heads / ff / vocab -> "model"  (megatron-style TP)
    embed    -> "data"    (FSDP weight sharding over the data axis)
    layers / lora / None  -> replicated

Divisibility-aware: a logical axis whose dimension does not divide the mesh
axis (e.g. 4 KV heads over model=16, or an odd vocab) silently degrades to
replication for that axis — the standard fallback (KV-head replication under
GQA-TP) — so every architecture maps onto the fixed production mesh without
per-arch special cases. When multiple logical axes in one tensor want the
same mesh axis, the first (leftmost priority order below) wins.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Priority-ordered: earlier entries claim their mesh axis first within a tensor.
LOGICAL_RULES: list[tuple[str, tuple[str, ...]]] = [
    ("experts", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("ff", ("model",)),
    ("vocab", ("model",)),
    ("embed", ("data",)),       # FSDP: weights gathered just-in-time
    ("layers", ()),
    ("lora", ()),
]
_RULES = dict(LOGICAL_RULES)
_PRIORITY = {name: i for i, (name, _) in enumerate(LOGICAL_RULES)}


def param_shard_count(mesh: Mesh) -> int:
    """Chips that hold one copy of the parameters between them: the mesh
    axes that LOGICAL_RULES shards over. A "pod" axis replicates."""
    axes = {a for _, targets in LOGICAL_RULES for a in targets}
    return math.prod(n for a, n in zip(mesh.axis_names, mesh.devices.shape)
                     if a in axes)


def resolve_spec(shape: tuple[int, ...], axes: tuple[str | None, ...],
                 mesh: Mesh, fsdp: bool = True) -> PartitionSpec:
    """Build a PartitionSpec for one tensor, enforcing divisibility and
    one-mesh-axis-per-tensor-dim / one-dim-per-mesh-axis."""
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    taken: set[str] = set()
    entries: list[str | None] = [None] * len(axes)
    # Resolve in priority order so e.g. "experts" claims "model" before "ff".
    order = sorted(range(len(axes)),
                   key=lambda i: _PRIORITY.get(axes[i] or "", 99))
    for i in order:
        name = axes[i]
        if name is None or name not in _RULES:
            continue
        if not fsdp and name == "embed":
            continue
        for mesh_axis in _RULES[name]:
            if mesh_axis not in mesh_sizes or mesh_axis in taken:
                continue
            if shape[i] % mesh_sizes[mesh_axis] != 0:
                continue  # degrade to replication (e.g. 4 kv-heads over 16)
            entries[i] = mesh_axis
            taken.add(mesh_axis)
            break
    return PartitionSpec(*entries)


def param_shardings(axes_tree, shapes_tree, mesh: Mesh, fsdp: bool = True):
    """Tree of NamedShardings parallel to the params tree."""

    def walk(ax, shp):
        if isinstance(ax, dict):
            return {k: walk(ax[k], shp[k]) for k in ax}
        return NamedSharding(mesh, resolve_spec(tuple(shp.shape), ax, mesh,
                                                fsdp=fsdp))

    return walk(axes_tree, shapes_tree)


def batch_spec(mesh: Mesh, seq_sharded: bool = False) -> PartitionSpec:
    """Token batches: batch over (pod, data); optionally sequence over data
    (context/sequence parallelism for the gb=1 long-context cells)."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if seq_sharded:
        return PartitionSpec(None, ("data",))
    return PartitionSpec(batch_axes if len(batch_axes) > 1 else batch_axes[0])


def cache_shardings(cache_tree, mesh: Mesh, shard_seq: bool = False):
    """KV-cache shardings. Layout per family (leading dim = layers):

    attention k/v (L, B, KVH, S, D), head-major: batch over (pod,data);
    kv-heads over model when divisible, otherwise the SEQUENCE dim shards
    over model — decode is bandwidth-bound, so spreading the cache across
    chips buys aggregate HBM bandwidth (the COPA 'compose more memory system
    around fixed compute' move); XLA turns the softmax reductions into psums.
    MLA latent caches (L, B, S, r) (no head dim) always sequence-shard.
    ``shard_seq`` (gb=1 long-context) shards S over data instead. SSM
    conv/ssm states: batch over (pod,data)."""
    dims = {a: s for a, s in zip(mesh.axis_names, mesh.devices.shape)}
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    nbatch = max(_flat(dims, batch_axes), 1)

    def model_ok(n: int) -> bool:
        return n % dims.get("model", 1) == 0

    def spec_for(name: str, arr) -> PartitionSpec:
        shape = arr.shape
        if name in ("k", "v", "shared_k", "shared_v", "cross_k", "cross_v",
                    "ckv", "krope"):
            latent = name in ("ckv", "krope")
            seq = 2 if latent else 3
            spec = [None] * (seq + 1)
            if shard_seq and shape[seq] % dims.get("data", 1) == 0:
                spec[seq] = "data"
            elif shape[1] % nbatch == 0 and shape[1] > 1:
                spec[1] = bspec
                if not latent and model_ok(shape[2]):
                    spec[2] = "model"
                elif model_ok(shape[seq]):
                    spec[seq] = "model"   # context-parallel over TP axis
            elif model_ok(shape[seq]):
                spec[seq] = "model"
            return PartitionSpec(*spec)
        # ssm conv/ssm states: (L, B, ...)
        if shape[1] % nbatch == 0 and shape[1] > 1:
            return PartitionSpec(None, bspec)
        return PartitionSpec()

    return {k: NamedSharding(mesh, spec_for(k, v)) for k, v in cache_tree.items()}


def _flat(dims: dict, axes: tuple) -> int:
    n = 1
    for a in axes:
        n *= dims.get(a, 1)
    return n


def constrain(x, *entries):
    """Best-effort ``with_sharding_constraint`` inside model code.

    ``entries`` are mesh-axis names, tuples of names, or None per dim. Axes
    not present in the ambient mesh, or not dividing the dim, degrade to
    None; with no mesh at all (CPU unit tests) this is a no-op. This is how
    model internals (e.g. MoE grouped tensors, sequence-parallel residual
    boundaries) pin their layout without plumbing shardings everywhere."""
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return x
        sizes = dict(zip(am.axis_names, am.axis_sizes))
    except Exception:  # noqa: BLE001
        return x
    resolved = []
    for dim, e in zip(x.shape, entries):
        if e is None:
            resolved.append(None)
            continue
        axes = (e,) if isinstance(e, str) else tuple(e)
        axes = tuple(a for a in axes if a in sizes)
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if not axes or dim % prod != 0:
            resolved.append(None)
        else:
            resolved.append(axes[0] if len(axes) == 1 else axes)
    if all(e is None for e in resolved):
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*resolved))


def sp_boundary(x):
    """Sequence-parallel residual boundary: (B, S, D) activations sharded
    batch->(pod,data), seq->model. Keeps the per-layer remat stash and all
    norm/elementwise work fully sharded (Megatron-SP, arXiv:2205.05198);
    the SPMD partitioner inserts the all-gather at QKV/FFN entry and the
    reduce-scatter after the output projections."""
    return constrain(x, ("pod", "data"), "model", None)

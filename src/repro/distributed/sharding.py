"""Logical-axis sharding: (axes pytree, ShardPlan, mesh) -> NamedShardings.

Rules (DESIGN.md §6):
  vocab      -> model     (unembed column parallel; vocab padded to %256)
  heads      -> model     (Q heads padded to a TP multiple, zero-masked)
  kv_heads   -> model IF n_kv % tp == 0 else replicated
  mlp        -> model     (column/row parallel FFN)
  expert     -> model IF n_experts % tp == 0 else replicated (TP inside expert)
  embed      -> data      (FSDP/ZeRO param sharding; XLA all-gathers per use)
  batch      -> (pod, data)
  cache_seq  -> model     (decode KV cache sequence sharding; softmax/contraction
                           over the sharded axis lowers to all-reduces)
  vocab_in   -> replicated (embedding table gather stays local)

Every mapping is divisibility-guarded against the actual dim, so odd sizes
degrade to replication instead of failing to compile.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _is_axes(x):
    return isinstance(x, tuple) or x is None


def _resolve(logical: str, plan, cfg) -> Optional[Any]:
    if logical is None:
        return None
    if logical == "batch":
        return tuple(plan.batch_axes) if plan.batch_axes else None
    if logical == "vocab_in":
        return None
    if logical == "kv_heads":
        return "model" if (cfg is not None and plan.shard_kv(cfg.n_kv_heads)) else None
    if logical == "expert":
        return "model" if (cfg is not None and plan.shard_experts(cfg.n_experts)) else None
    if logical == "cache_seq":
        return "model"
    return plan.axis_for(logical)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def pspec_for(shape, axes, plan, mesh: Mesh, cfg=None) -> P:
    """PartitionSpec for one array, with divisibility + duplicate-axis guards."""
    if axes is None:
        return P()
    assert len(axes) == len(shape), f"axes {axes} vs shape {shape}"
    used: set[str] = set()
    out = []
    for dim, logical in zip(shape, axes):
        entry = _resolve(logical, plan, cfg)
        if entry is None:
            out.append(None)
            continue
        flat = entry if isinstance(entry, tuple) else (entry,)
        if any(a in used for a in flat):
            out.append(None)  # mesh axis already consumed by an earlier dim
            continue
        if dim % _axis_size(mesh, entry) != 0:
            out.append(None)  # not divisible -> replicate
            continue
        used.update(flat)
        out.append(entry)
    return P(*out)


def shardings_for(tree, axes_tree, plan, mesh: Mesh, cfg=None):
    """NamedSharding pytree for (params-like tree, parallel axes tree).

    ``tree`` may hold arrays or ShapeDtypeStructs (dry-run path).
    """
    def one(x, ax):
        return NamedSharding(mesh, pspec_for(x.shape, ax, plan, mesh, cfg))

    return jax.tree.map(one, tree, axes_tree,
                        is_leaf=lambda x: _is_axes(x) if x is not tree else False)


def tree_shardings(tree, axes_tree, plan, mesh: Mesh, cfg=None):
    """Like shardings_for but walks the two trees in lockstep explicitly
    (axes leaves are tuples/None, which jax.tree.map would descend into)."""
    if isinstance(tree, dict):
        return {k: tree_shardings(tree[k], axes_tree[k], plan, mesh, cfg)
                for k in tree}
    if isinstance(tree, (list,)):
        return [tree_shardings(t, a, plan, mesh, cfg)
                for t, a in zip(tree, axes_tree)]
    if _is_axes(axes_tree) and hasattr(tree, "shape"):
        return NamedSharding(mesh, pspec_for(tree.shape, axes_tree, plan, mesh, cfg))
    raise TypeError(f"mismatched trees: {type(tree)} vs {type(axes_tree)}")


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for host->device staging of a dim-0-batched array."""
    return NamedSharding(mesh, P(axis))


def data_parallel(fn, mesh: Mesh, axis: str = "data"):
    """shard_map-wrap ``fn(params, batch) -> out`` over the mesh's data axis.

    The serve-engine layout (DESIGN.md §7): params replicated (P() prefix
    spec), dim 0 of every batch input and output sharded across ``axis`` —
    each device runs the per-shard forward on its slice of the co-batched
    requests, the direct analogue of the paper's §II-A independent kernel
    windows on parallel SOT-MRAM sub-arrays.  ``fn`` must be per-sample
    independent (no cross-batch reductions); the serve forwards guarantee
    that (per-sample norm statistics, per-request KV caches).

    The dispatched batch must be divisible by the axis size — the engine's
    padding buckets guarantee it (`_pad_to` rounds up to the device count).
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=P(axis), check_vma=False)


def batch_pspec(plan, ndim: int, batch_dim: int = 0) -> P:
    spec = [None] * ndim
    spec[batch_dim] = tuple(plan.batch_axes) if plan.batch_axes else None
    return P(*spec)


def batch_shardings(batch_tree, plan, mesh: Mesh):
    """Shard dim 0 of every leaf over the batch axes (divisibility-guarded)."""
    def one(x):
        bax = tuple(plan.batch_axes) if plan.batch_axes else None
        if bax is None or x.ndim == 0 or x.shape[0] % _axis_size(mesh, bax) != 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(bax, *([None] * (x.ndim - 1))))

    return jax.tree.map(one, batch_tree)

"""Sharding rules: param/activation PartitionSpecs for every family.

One generic rule engine instead of per-arch tables: tensors are classified by
their path (e.g. ``("layers", "attn", "wq", "w")``) and each class lists
candidate specs in priority order; the first whose sharded dims all divide
evenly into the mesh axes wins (vocab 50280 on a 16-way axis silently falls
back to replicated, qwen2-moe's 60 experts fall back from EP to TP, etc.).

Scheme (DESIGN.md §5):
  * 2D "hybrid FSDP x TP": matmul weights shard the parallel dim over
    ``model`` (TP) and the other dim over ``data`` (FSDP) when fsdp=True;
  * MoE experts shard over ``model`` (EP) when the expert count divides,
    otherwise per-expert FFN dims shard over ``model`` (TP);
  * batch dims shard over ("pod","data"); KV caches shard batch over data
    and sequence over model (context-sharded decode).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def _fits(shape: Tuple[int, ...], spec: P, mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    for dim, entry in zip(shape, entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        k = int(np.prod([sizes[a] for a in axes]))
        if dim % k != 0:
            return False
    return True


def _pick(shape, mesh, *candidates) -> P:
    for spec in candidates:
        if _fits(shape, spec, mesh):
            return spec
    return P()


def _pad_rank(spec: P, rank: int, stacked: int) -> P:
    """Prefix ``stacked`` Nones (layer axes) and right-pad to rank."""
    inner = tuple(spec)
    return P(*((None,) * stacked + inner +
               (None,) * (rank - stacked - len(inner))))


def params_shardings(param_shapes, mesh, fsdp: bool = True):
    """Map a pytree of ShapeDtypeStructs -> NamedShardings."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes)
    out = []
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        spec = param_spec_resolved(keys, leaf.shape, mesh, fsdp)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def param_spec_resolved(path, shape, mesh, fsdp) -> P:
    """param_spec with shape-driven resolution of the stacked prefix."""
    names = [p for p in path if isinstance(p, str)]
    leaf = names[-1] if names else ""
    parent = names[-2] if len(names) >= 2 else ""
    # determine base rank from tensor kind. MoE expert tensors are *bare*
    # arrays named up/gate/down (E, d, f); dense MLP weights are nested one
    # level deeper as {"up": {"w": ...}} — so a leaf literally named
    # up/gate/down is always an expert stack.
    if leaf in ("conv_b", "A_log", "dt_bias", "D", "scale", "bias", "b"):
        base_rank = 1
    elif leaf in ("embed", "head", "router", "conv_w"):
        base_rank = 2
    elif leaf in ("up", "gate", "down"):
        base_rank = 3
    elif leaf == "w" or parent in ("wq", "wk", "wv", "wo", "up", "gate",
                                   "down", "in_proj", "out_proj"):
        base_rank = 2
    else:
        base_rank = min(len(shape), 2)
    stacked = max(len(shape) - base_rank, 0)
    base = shape[stacked:]
    f = "data" if (fsdp and "data" in mesh.axis_names) else None

    def pick(*cands):
        return _pad_rank(_pick(base, mesh, *cands), len(shape), stacked)

    if leaf == "embed":
        return pick(P("model", f), P("model", None), P(None, f), P())
    if leaf == "head":
        return pick(P(f, "model"), P(None, "model"), P(f, None), P())
    if leaf == "router":
        return pick(P(f, None), P())
    if leaf == "conv_w":
        return pick(P(None, "model"), P())
    if leaf in ("conv_b", "A_log", "dt_bias", "D"):
        return pick(P("model"), P())
    if parent == "out_norm" and leaf == "scale":
        return pick(P("model"), P())
    if leaf in ("scale", "bias"):
        return P()
    if base_rank == 3:                      # moe expert tensors
        if leaf in ("up", "gate"):
            return pick(P("model", f, None), P(None, f, "model"), P())
        if leaf == "down":
            return pick(P("model", None, f), P(None, "model", f), P())
    if parent in ("wq", "wk", "wv", "up", "gate", "in_proj"):
        if leaf == "b":
            return pick(P("model"), P())
        return pick(P(f, "model"), P(None, "model"), P(f, None), P())
    if parent in ("wo", "down", "out_proj"):
        if leaf == "b":
            return P()
        return pick(P("model", f), P("model", None), P(None, f), P())
    return P()


def _looks_moe(names) -> bool:
    return "ffn_moe" in names or "ffn" in names


def batch_spec(mesh) -> P:
    return P(("pod", "data") if "pod" in mesh.axis_names else "data")


def batch_shardings(batch_shapes, mesh, dim: int = 0):
    """Inputs: shard the global-batch dim over (pod, data); rest replicated.
    ``dim=1`` handles the (microbatches, B/M, ...) layout. Falls back to
    fewer axes when the dim doesn't divide (e.g. 16-seq microbatches on a
    32-way pod x data product shard over data only)."""
    candidates = [tuple(batch_spec(mesh))[0]]
    if "pod" in mesh.axis_names:
        candidates += ["data", "pod"]

    def one(leaf):
        for b in candidates:
            if len(leaf.shape) > dim \
                    and leaf.shape[dim] % _axis_size(mesh, b) == 0:
                return NamedSharding(mesh, P(*((None,) * dim), b))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, batch_shapes)


def cache_shardings(cache_shapes, mesh):
    """KV caches -> batch over data, sequence over model: the decode caches
    are head-major (L, B, KH, S, D), the cross-attention caches (L, B, S,
    KH, D).

    SSM states (L, B, ...): batch over data. Scalars replicated.
    """
    b = tuple(batch_spec(mesh))[0]

    def one(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        shape = leaf.shape
        if not shape:                                   # pos scalar
            return NamedSharding(mesh, P())
        cands = []
        if names and names[-1] in ("k", "v", "kv_k", "kv_v", "cross_k",
                                   "cross_v", "k_global", "v_global",
                                   "k_local", "v_local"):
            # batch over data + sequence over model; batch=1 (long_500k)
            # falls back to pure context sharding
            heads = () if names[-1].startswith("cross") else (None,)
            cands = [P(None, b, *heads, "model"),
                     P(None, None, *heads, "model"), P(None, b), P()]
        elif len(shape) >= 2:
            cands = [P(None, b), P()]
        else:
            cands = [P()]
        return NamedSharding(mesh, _pick(shape, mesh, *cands))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    out = [one(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def _axis_size(mesh, entry) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = entry if isinstance(entry, tuple) else (entry,)
    return int(np.prod([sizes[a] for a in axes]))


def sharded_size_bytes(shapes, shardings) -> int:
    """Per-device bytes of a sharded pytree (exact, backend-independent)."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(shardings)):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        shards = sh.num_devices_sharded_over(leaf.shape) \
            if hasattr(sh, "num_devices_sharded_over") else None
        if shards is None:
            shards = _spec_shards(leaf.shape, sh.spec, sh.mesh)
        total += n * leaf.dtype.itemsize // shards
    return total


def _spec_shards(shape, spec, mesh) -> int:
    k = 1
    entries = tuple(spec)
    for dim, entry in zip(shape, entries):
        if entry is None:
            continue
        k *= _axis_size(mesh, entry)
    return k

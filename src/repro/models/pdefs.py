"""Declarative parameter definitions.

A model's parameters are described once as a pytree of :class:`ParamDef`
(shape + logical axes + init rule). From that single source of truth we derive:

* real initialized params        (``init_from_defs``)
* abstract ShapeDtypeStructs     (``abstract_from_defs``) — used by the dry-run
* PartitionSpecs for a mesh      (``pspecs_from_defs``) — divisibility-aware

Logical axis names used across the codebase:
  "embed"     d_model dim               -> sharded over "data" (FSDP)
  "vocab"     vocabulary dim            -> "model"
  "ff"        mlp hidden dim            -> "model"
  "heads"     q heads (or fused h*hd)   -> "model"
  "kv_heads"  kv heads                  -> "model"
  "experts"   MoE expert dim            -> "model"
  "layers"    scanned layer stack       -> replicated
  "lora"      low-rank adapters, states -> replicated
  None        replicated
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"          # normal | zeros | ones | scaled | embed
    fan_in_dims: Tuple[int, ...] = ()   # dims contributing to fan-in (default: all but last)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pdef(x) -> bool:
    return isinstance(x, ParamDef)


def _tmap(f, tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=is_pdef)


def _tmap_with_path(f, tree):
    return jax.tree_util.tree_map_with_path(f, tree, is_leaf=is_pdef)


def stack(defs, n: int):
    """Add a leading scanned-layers axis of size n to every ParamDef."""
    return _tmap(
        lambda d: dataclasses.replace(d, shape=(n,) + d.shape, axes=("layers",) + d.axes),
        defs,
    )


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init_leaf(key, d: ParamDef):
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    fan_dims = d.fan_in_dims or tuple(range(max(len(d.shape) - 1, 1)))
    # scanned stacks: the leading "layers" axis never counts toward fan-in
    fan = 1
    for i in fan_dims:
        if i < len(d.shape) and d.axes[i] != "layers":
            fan *= d.shape[i]
    if d.init == "embed":
        scale = 1.0
    else:
        scale = 1.0 / np.sqrt(max(fan, 1))
    return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(d.dtype)


def init_from_defs(defs, key):
    """Initialize real parameters. Keys are derived per-path (stable)."""
    def f(path, d):
        pstr = jax.tree_util.keystr(path)
        # crc32, not hash(): str hashes are salted per process, and weights
        # made from a seed must be the same in every process
        sub = jax.random.fold_in(key, zlib.crc32(pstr.encode()) % (2**31))
        return _init_leaf(sub, d)
    return _tmap_with_path(f, defs)


def abstract_from_defs(defs):
    return _tmap(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------

# logical axis -> preferred mesh axis (in priority order); divisibility-checked
DEFAULT_RULES = {
    "vocab": ("model",),
    "ff": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    "embed": ("data",),          # FSDP weight sharding
    "layers": (),
    "lora": (),
    "batch": ("pod", "data"),
    "cache_seq": (),
    "frames": (),
    # paged KV arenas: replicated today; a multi-host sharded arena would
    # shard "pages" over ("pod", "data") once page ids are mesh-local
    "pages": (),
    "page_seq": (),
}


def resolve_axes(axes, shape, mesh: Mesh, rules=None) -> PartitionSpec:
    """Map logical axes to a PartitionSpec, dropping non-dividing or duplicate
    mesh axes (a mesh axis may appear at most once in a spec)."""
    rules = rules if rules is not None else DEFAULT_RULES
    used: set = set()
    out = []
    for size, ax in zip(shape, axes):
        picked = None
        if ax is not None:
            candidates = rules.get(ax, ())
            if isinstance(candidates, str):
                candidates = (candidates,)
            # multi-axis sharding for one dim (e.g. batch over (pod, data))
            multi = []
            prod = 1
            for cand in candidates:
                if cand in used or cand not in mesh.shape:
                    continue
                if size % (prod * mesh.shape[cand]) == 0:
                    multi.append(cand)
                    prod *= mesh.shape[cand]
            if multi:
                for m in multi:
                    used.add(m)
                picked = tuple(multi) if len(multi) > 1 else multi[0]
        out.append(picked)
    # trim trailing Nones for readability
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def pspecs_from_defs(defs, mesh: Mesh, rules=None):
    return _tmap(lambda d: resolve_axes(d.axes, d.shape, mesh, rules), defs)


def shardings_from_defs(defs, mesh: Mesh, rules=None):
    return _tmap(lambda d: NamedSharding(mesh, resolve_axes(d.axes, d.shape, mesh, rules)), defs)


def count_params(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_pdef)
    return int(sum(int(np.prod(d.shape)) for d in leaves))


__all__ = [
    "ParamDef", "stack", "init_from_defs", "abstract_from_defs",
    "pspecs_from_defs", "shardings_from_defs", "resolve_axes",
    "count_params", "DEFAULT_RULES", "is_pdef",
]

"""Activation/parameter sharding: logical axis names -> mesh axes.

The model code annotates activations with *logical* axes ("batch", "seq",
"heads", ...). This module maps them onto whatever physical mesh is active:

    single pod   (data=16, model=16)
    multi pod    (pod=2, data=16, model=16)   — "pod" composes with "data"

Outside a mesh context every helper is a no-op, so the same model code runs
un-sharded on one CPU device (smoke tests) and sharded under pjit.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred physical mesh axes (first match present in mesh
# wins for each name; tuples mean "shard over the product of these axes")
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),      # data parallel (pods stack with data axis)
    "heads": ("model",),           # tensor parallel over attention heads
    "kv_heads": ("model",),        # falls back to replicated if too few heads
    "ffn": ("model",),             # tensor parallel over the MLP hidden dim
    "vocab": ("model",),           # embedding / logits vocab sharding
    "fsdp": ("pod", "data"),       # zero-style param sharding axis
    "seq_shard": ("model",),       # opt-in sequence/context parallelism
    "embed": (),                   # replicated
    "seq": (),
    "expert": (),                  # experts TP'd internally, not EP by default
    "pages": (),                   # page pool replicated over data; kv_heads
                                   # split it over "model" (see page_pool_specs)
}

_state = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def current_rules() -> dict:
    over = getattr(_state, "rules", None)
    return {**LOGICAL_RULES, **(over or {})}


@contextlib.contextmanager
def mesh_context(mesh: Mesh | None, rules: dict | None = None):
    """Activate a mesh (and optional logical-rule overrides — e.g.
    attention-free archs shard "batch" over the idle "model" axis too)."""
    prev = current_mesh()
    prev_rules = getattr(_state, "rules", None)
    _state.mesh = mesh
    _state.rules = rules
    try:
        yield
    finally:
        _state.mesh = prev
        _state.rules = prev_rules


def _axes_for(name: str | None, dim_size: int, mesh: Mesh,
              rules: dict | None = None) -> tuple[str, ...] | None:
    """Resolve one logical dim: keep only mesh axes that exist and whose
    product divides dim_size (otherwise replicate — e.g. kv_heads=2 on
    model=16)."""
    if name is None:
        return None
    want = (rules or current_rules()).get(name, ())
    axes = tuple(a for a in want if a in mesh.axis_names)
    if not axes:
        return None
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
    if dim_size % prod != 0:
        # drop axes from the end until it divides (keep the biggest prefix)
        while axes and dim_size % prod != 0:
            prod //= mesh.shape[axes[-1]]
            axes = axes[:-1]
        if not axes or dim_size % prod != 0:
            return None
    return axes if len(axes) > 1 else axes  # tuple form kept


def logical_spec(logical: tuple[str | None, ...], shape: tuple[int, ...],
                 mesh: Mesh) -> P:
    parts = []
    used: set[str] = set()
    for name, size in zip(logical, shape):
        axes = _axes_for(name, size, mesh)
        if axes is None:
            parts.append(None)
            continue
        axes = tuple(a for a in axes if a not in used)
        if not axes:
            parts.append(None)
            continue
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if size % prod != 0:
            parts.append(None)
            continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    return P(*parts)


def act_shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain an activation's sharding by logical dim names (no-op without
    an active mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_spec(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *parts) -> NamedSharding:
    return NamedSharding(mesh, P(*parts))


# ---------------------------------------------------------------------------
# Paged KV cache sharding (core/paging.py)
#
# The page pool is the serving-time analogue of the contiguous cache's
# (batch -> data, kv_heads -> model) layout, except the page axis REPLACES
# the batch axis as the capacity dimension: pages are not owned by a mesh
# axis (any row may map any page), so the pool replicates over "data" and
# shards its kv_heads dim over "model". Page tables and lengths are
# batch-sharded host metadata; the free list is replicated allocator state.
# ---------------------------------------------------------------------------

PAGE_POOL_LOGICAL: dict[str, tuple[str | None, ...]] = {
    "k_q": ("pages", "kv_heads", None, None),   # (n_pages, H_kv, ps, D)
    "v_q": ("pages", "kv_heads", None, None),
    "k_s": ("pages", "kv_heads", None, None),   # (n_pages, H_kv, 1, D)
    "v_s": ("pages", "kv_heads", None, None),
    "free_stack": (None,),
    "n_free": (),
}

PAGED_CACHE_LOGICAL: dict[str, tuple[str | None, ...]] = {
    "page_table": ("batch", None),              # (B, max_blocks)
    "resid_k": ("batch", "kv_heads", None, None),
    "resid_v": ("batch", "kv_heads", None, None),
    "length": ("batch",),
}


def page_pool_specs(pool, mesh: Mesh):
    """PartitionSpec pytree for a `PagePool` (same structure as the pool)."""
    import dataclasses as _dc
    return _dc.replace(pool, **{
        f: logical_spec(PAGE_POOL_LOGICAL[f], getattr(pool, f).shape, mesh)
        for f in PAGE_POOL_LOGICAL})


def paged_cache_specs(cache, mesh: Mesh):
    """PartitionSpec pytree for a `PagedQuantizedKVCache`: pool leaves via
    `page_pool_specs`, view leaves batch-sharded. Feed to NamedSharding /
    jax.device_put / pjit in_shardings."""
    import dataclasses as _dc
    return _dc.replace(
        cache, pool=page_pool_specs(cache.pool, mesh), **{
            f: logical_spec(PAGED_CACHE_LOGICAL[f],
                            getattr(cache, f).shape, mesh)
            for f in PAGED_CACHE_LOGICAL})


def paged_cache_shardings(cache, mesh: Mesh):
    """NamedSharding pytree matching `paged_cache_specs`."""
    specs = paged_cache_specs(cache, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))

"""Sharding resolution: logical axis rules -> PartitionSpecs.

This is the runtime mirror of ``DataOrganizationPass._resolve``: the pass
repairs the *IR*'s placements; these helpers apply the same two rules to
*runtime* pytrees (params, inputs, caches) whose shapes may differ from
the IR (padded heads/vocab, reduced smoke configs):

1. divisibility repair — an assignment that does not divide the dim is
   dropped (the tensor stays replicated on that dim);
2. uniqueness — a mesh axis may shard at most one dim of a tensor (first
   dim wins, matching the pass).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def mesh_sizes(mesh: Any) -> Dict[str, int]:
    """``{axis_name: size}`` for a jax Mesh, a MeshModel, or a dict."""
    if isinstance(mesh, Mapping):
        return dict(mesh)
    shape = getattr(mesh, "shape", None)
    if isinstance(shape, Mapping):          # jax.sharding.Mesh
        return dict(shape)
    axes = getattr(mesh, "axes", None) or getattr(mesh, "axis_names", None)
    if axes is None or shape is None:
        raise TypeError(
            f"mesh_sizes: unsupported mesh-like object "
            f"{type(mesh).__name__!r}: expected a Mapping "
            "{axis: size}, a jax.sharding.Mesh (`.shape` mapping), or a "
            "MeshModel-like object with `.axes`/`.axis_names` and a "
            f"`.shape` tuple (got axes={axes!r}, shape={shape!r})")
    axes, shape = tuple(axes), tuple(shape)
    if len(axes) != len(shape):
        raise TypeError(
            f"mesh_sizes: {type(mesh).__name__!r} has {len(axes)} axis "
            f"names {axes} but a {len(shape)}-entry shape {shape}")
    return dict(zip(axes, shape))


def _names(assign: Any) -> Tuple[str, ...]:
    if assign is None:
        return ()
    if isinstance(assign, str):
        return (assign,)
    return tuple(assign)


def resolve_pspec(rules: Mapping[str, Any], shape: Sequence[int],
                  axes: Sequence[Optional[str]],
                  sizes: Mapping[str, int]) -> P:
    """Resolve one tensor's logical axes through the plan's axis rules.

    ``rules`` maps logical axis -> mesh assignment (name, tuple of names,
    or None); ``axes`` names each dim of ``shape`` (None = never sharded);
    ``sizes`` is the mesh's ``{axis: size}``.  Divisibility repair and
    mesh-axis uniqueness are applied exactly as the data-organization
    pass does for IR tensors.
    """
    entries = []
    for dim, ax in zip(shape, axes):
        assign = rules.get(ax) if ax is not None else None
        names = tuple(n for n in _names(assign) if n in sizes)
        if not names:
            entries.append(None)
            continue
        factor = math.prod(sizes[n] for n in names)
        entries.append(names if factor and dim % factor == 0 else None)
    seen: set = set()
    out = []
    for e in entries:
        if e is None:
            out.append(None)
            continue
        keep = tuple(n for n in e if n not in seen)
        seen.update(keep)
        out.append(keep[0] if len(keep) == 1 else (keep or None))
    return P(*out)


def tree_shardings(mesh: jax.sharding.Mesh, pspecs: Any) -> Any:
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


#: runtime cache pytree -> logical axes (matches core.describe's decls)
CACHE_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "pos": ("batch",),                  # per-slot (B,) decode offsets
    "k": ("layers", "batch", "seq_kv", "kv_heads", "head_dim"),
    "v": ("layers", "batch", "seq_kv", "kv_heads", "head_dim"),
    "ckv": ("layers", "batch", "seq_kv", None),    # MLA latent rows
    "ssm": ("layers", "batch", "ssm_heads", None, None),
    "conv": ("layers", "batch", None, "ssm_inner"),
}

#: paged residency: k/v are block pools (no batch/seq dims — the pool
#: dim is the unit of placement) and the block table rides the batch dim
PAGED_CACHE_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "pos": ("batch",),
    "k": ("layers", "kv_blocks", None, "kv_heads", "head_dim"),
    "v": ("layers", "kv_blocks", None, "kv_heads", "head_dim"),
    "ckv": ("layers", "kv_blocks", None, None),
    "block_tbl": ("batch", None),
    "ssm": ("layers", "batch", "ssm_heads", None, None),
    "conv": ("layers", "batch", None, "ssm_inner"),
}


def cache_pspecs(plan, arch, cache_shapes: Mapping[str, Any],
                 sizes: Mapping[str, int]) -> Dict[str, P]:
    """PartitionSpecs for the session-cache pytree.

    Starts from the plan's axis rules and overlays the per-tensor
    placement the data-organization pass decided for ``cache.*`` (that is
    where the seq-vs-head_dim spill for flash-decode lives), then
    re-applies divisibility repair against the *runtime* shapes (padded
    kv/ssm heads may differ from the IR).

    A paged cache (marked by its ``block_tbl`` entry) resolves through
    :data:`PAGED_CACHE_AXES`: the IR placement's seq-dim spill translates
    to the pool dim (``seq_kv -> kv_blocks`` — the paged analogue the
    :func:`repro.dist.flash_decode.flash_decode_paged` combine serves).
    When that combine will run its 2-D path (data degree divides both
    the batch and, jointly with the model degree, the pool —
    :func:`repro.dist.flash_decode.pool_sharding_kind` is the shared
    predicate), the pool dim shards data-major over ``(data..., model)``
    so the placement matches the shard_map's in_specs instead of
    resharding every tick.
    """
    paged = "block_tbl" in cache_shapes
    axes_map = PAGED_CACHE_AXES if paged else CACHE_AXES
    pool_2d = None
    if paged and "k" in cache_shapes:
        from repro.dist.flash_decode import pool_sharding_kind
        dnames = tuple(a for a in plan.mesh_axes
                       if a != "model" and a in sizes)
        n_blocks = cache_shapes["k"].shape[1]
        batch = cache_shapes["block_tbl"].shape[0]
        if pool_sharding_kind(dict(sizes), n_blocks, batch,
                              data_axes=dnames) == "2d":
            pool_2d = dnames + (("model",) if "model" in sizes else ())
    out: Dict[str, P] = {}
    for key, sds in cache_shapes.items():
        axes = axes_map.get(key, tuple(None for _ in sds.shape))
        rules = dict(plan.axis_rules)
        rules.setdefault("kv_blocks", None)
        placed = plan.placements.get(f"cache.{key}")
        if placed is not None and placed.spec:
            ir_axes = CACHE_AXES.get(key, axes)   # placements follow the IR
            for ax, assign in zip(ir_axes, placed.spec):
                if ax == "seq_kv" and paged:
                    ax = "kv_blocks"
                if ax is not None:
                    rules[ax] = assign
        if pool_2d is not None and key in ("k", "v"):
            rules["kv_blocks"] = pool_2d
        out[key] = resolve_pspec(rules, sds.shape, axes, sizes)
    return out

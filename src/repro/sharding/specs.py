"""Logical-dims → mesh-axes mapping (DP / FSDP / TP / EP / SP).

Every parameter leaf is created with a tuple of *logical dim names*
(``repro.models.layers.ParamBuilder``). This module maps those names onto
mesh axes, with divisibility-checked fallbacks, producing ``PartitionSpec``
trees for ``jax.jit`` in/out shardings.

Activation sharding inside model code goes through ``constrain(x, dims)``,
which is a no-op unless an ``activation_sharding(axes)`` context is active
(set by the launcher while tracing).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Which mesh axes implement each parallelism flavour."""
    dp: Tuple[str, ...]              # batch axes (("pod","data") or ("data",))
    fsdp: Optional[str]              # param-shard axis (subset of dp) or None
    tp: Optional[str]                # tensor-parallel axis
    ep: Optional[str]                # expert-parallel axis
    sp: Optional[str]                # sequence-shard axis (long prefill)
    sizes: Mapping[str, int]         # axis name -> size

    def size(self, ax: Optional[str]) -> int:
        return 1 if ax is None else self.sizes[ax]


def make_axes(mesh: jax.sharding.Mesh, *, use_fsdp: bool = False,
              seq_shard: bool = False) -> MeshAxes:
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    dp = tuple(n for n in names if n in ("pod", "data"))
    tp = "model" if "model" in names else None
    return MeshAxes(
        dp=dp,
        fsdp="data" if (use_fsdp and "data" in names) else None,
        tp=tp,
        ep=tp,
        sp=tp if seq_shard else None,
        sizes=sizes,
    )


# Logical param-dim name -> which MeshAxes field shards it. Names ending in
# "_nt" are never sharded (small / replicated tensors).
_PARAM_RULES = {
    "vocab": "tp",
    "embed": "fsdp",
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,        # fallback target — see combined rule below
    "ff": "tp",
    "experts": "ep",
    "moe_embed": "fsdp",
    "moe_ff": None,
    "ssm_inner": "tp",
    "xl_inner": "tp",
    "xl_inner2": None,
    "layers": None,
    # activation/cache dims (serve-state leaves)
    "batch": "dp",
    "kvseq": "dp",     # context-parallel KV when batch can't shard (long_500k)
}


def _axis_for(name: Optional[str], axes: MeshAxes) -> Optional[str]:
    if name is None or name.endswith("_nt"):
        return None
    field = _PARAM_RULES.get(name)
    if field is None:
        return None
    return getattr(axes, field)


def leaf_spec(dims: Tuple[Optional[str], ...], shape: Tuple[int, ...],
              axes: MeshAxes) -> P:
    """PartitionSpec for one leaf, with divisibility fallbacks.

    Combined rule: if a ``heads``/``kv_heads`` dim is not divisible by the tp
    axis, tp falls back to that leaf's ``head_dim`` dim (if divisible) — the
    standard GQA layout escape when head counts don't divide TP.
    """
    assignment: list = [None] * len(dims)
    used: set = set()

    def try_assign(i: int, ax: Optional[str]) -> bool:
        if ax is None:
            return False
        ax_t = ax if isinstance(ax, tuple) else (ax,)
        total = math.prod(axes.size(a) for a in ax_t)
        if any(a in used for a in ax_t):
            return False
        if shape[i] % total != 0 or total == 1:
            return False
        assignment[i] = ax if not isinstance(ax, tuple) else ax_t
        used.update(ax_t)
        return True

    head_fallback_needed = False
    for i, name in enumerate(dims):
        ax = _axis_for(name, axes)
        ok = try_assign(i, ax)
        # Q heads fall back to head_dim sharding. KV *projection weights*
        # whose head count doesn't divide TP are REPLICATED (hd-sharding
        # them forces replicate-then-reshard copies at the GQA einsum —
        # §Perf iteration A). KV *caches* ("kvseq" present) keep the
        # head_dim fallback: replicating a 32k-half-MB-per-token cache
        # would be catastrophic (§Perf decode iterations).
        if not ok and axes.tp and (
                name == "heads"
                or (name == "kv_heads" and "kvseq" in dims)):
            head_fallback_needed = True
    if head_fallback_needed and axes.tp not in used:
        for i, name in enumerate(dims):
            if name == "head_dim" and try_assign(i, axes.tp):
                break
    return P(*assignment)


def param_specs(dims_tree: Any, shapes_tree: Any, axes: MeshAxes) -> Any:
    """Map matching (dims, shape-struct) pytrees to a PartitionSpec pytree."""
    def one(dims, shaped):
        shape = shaped.shape if hasattr(shaped, "shape") else tuple(shaped)
        return leaf_spec(tuple(dims), tuple(shape), axes)
    return jax.tree.map(one, dims_tree, shapes_tree,
                        is_leaf=lambda d: isinstance(d, tuple))


# ---------------------------------------------------------------------------
# Activation sharding context
# ---------------------------------------------------------------------------

_ACTIVE: Optional[MeshAxes] = None


def active_axis_size(kind: str) -> int:
    """Size of the active context's axis ("tp"/"dp"/...), 1 if no context."""
    if _ACTIVE is None:
        return 1
    ax = getattr(_ACTIVE, kind, None)
    if ax is None:
        return 1
    ax_t = ax if isinstance(ax, tuple) else (ax,)
    return math.prod(_ACTIVE.size(a) for a in ax_t)


def sharding_active() -> bool:
    """Whether an ``activation_sharding`` context is active."""
    return _ACTIVE is not None


@contextlib.contextmanager
def activation_sharding(axes: Optional[MeshAxes]):
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, axes
    try:
        yield
    finally:
        _ACTIVE = prev


def constrain(x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
    """Constrain activation sharding. dims entries: "dp"|"sp"|"tp"|None."""
    axes = _ACTIVE
    if axes is None:
        return x
    spec: list = []
    used: set = set()
    for i, d in enumerate(dims):
        ax = {"dp": axes.dp, "sp": axes.sp, "tp": axes.tp, "ep": axes.ep,
              None: None}[d]
        if ax is None:
            spec.append(None)
            continue
        ax_t = ax if isinstance(ax, tuple) else (ax,)
        total = math.prod(axes.size(a) for a in ax_t)
        if total == 1 or any(a in used for a in ax_t) or x.shape[i] % total:
            spec.append(None)
        else:
            spec.append(ax if not isinstance(ax, tuple) else ax_t)
            used.update(ax_t)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Gang rank regions (reshard-on-restore)
# ---------------------------------------------------------------------------
# A gang job's global state is partitioned over its ranks along one axis
# (rows of the lead dimension, like a 1-D data-parallel mesh). These
# helpers are the single source of truth for that partition on BOTH sides:
# the gang writer stamps each rank's chunk at its region's global offset,
# and the gang restore recomputes regions for a *different* rank count —
# the reader's region-overlap assembly then reshards for free.

def even_regions(dim: int, n: int) -> List[Tuple[int, int]]:
    """Split ``dim`` rows over ``n`` ranks: [(offset, length)] per rank.

    The remainder spreads over the leading ranks (lengths differ by at
    most 1), every row is owned by exactly one rank, and the split is a
    pure function of (dim, n) — deterministic across save and restore.
    """
    if n <= 0:
        raise ValueError(f"need at least one rank, got {n}")
    base, rem = divmod(dim, n)
    regions, off = [], 0
    for r in range(n):
        length = base + (1 if r < rem else 0)
        regions.append((off, length))
        off += length
    return regions


def rank_region(shape: Tuple[int, ...], n_ranks: int, rank: int,
                axis: int = 0) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """One rank's (offset, shape) of a global array sharded along ``axis``."""
    off, length = even_regions(shape[axis], n_ranks)[rank]
    offset = tuple(off if i == axis else 0 for i in range(len(shape)))
    shp = tuple(length if i == axis else d for i, d in enumerate(shape))
    return offset, shp


def owner_of_row(dim: int, n_ranks: int, row: int) -> int:
    """Which rank owns ``row`` under ``even_regions(dim, n_ranks)`` —
    used to re-route drained in-flight messages after a reshard."""
    for r, (off, length) in enumerate(even_regions(dim, n_ranks)):
        if off <= row < off + length:
            return r
    raise ValueError(f"row {row} outside [0, {dim})")

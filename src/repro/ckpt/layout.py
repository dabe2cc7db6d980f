"""Topology-agnostic checkpoint layout: pytree <-> named chunks + manifest.

The manifest records, per leaf: global shape, dtype, and a list of chunks
addressed by *global offsets* — never mesh coordinates. Any process on any
mesh can therefore restore any leaf under any sharding by reading the
overlapping chunks (reader.py). This is the paper's "compile for the common
denominator" portability rule applied to device topologies (DESIGN.md §2).

Two chunk layouts coexist (see docs/architecture.md):
  * format v1 (legacy): chunks live under their step directory
    (``<prefix>/step_<n>/chunks/<leaf>::o<off>``) and are private to one step.
  * format v2 (content-addressed): chunks live in a shared namespace keyed by
    the blake2b digest of their *encoded* bytes
    (``<prefix>/cas/<digest>``) and may be shared by any number of steps —
    the substrate for incremental checkpointing (writer.py skips the put for
    any chunk whose digest is already stored).
``Manifest.from_json`` loads both; v1 manifests simply carry ``hash=None``
chunks.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.obs.trace import tracer

try:
    import ml_dtypes
except ImportError:                               # pragma: no cover
    ml_dtypes = None

MANIFEST = "MANIFEST.json"
COMMITTED = "COMMITTED"
CAS_DIR = "cas"
FORMAT_VERSION = 2                    # content-addressed chunks


def np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        if ml_dtypes is not None:
            return np.dtype(getattr(ml_dtypes, name))
        raise


# ---------------------------------------------------------------------------
# Paths
# ---------------------------------------------------------------------------

def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    return str(k)


def leaf_items(tree: Any) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(_key_str(k) for k in path), leaf) for path, leaf in flat]


def structure_skeleton(tree: Any) -> Any:
    """JSON-serializable skeleton for target-free restores."""
    if isinstance(tree, dict):
        return {"!kind": "dict",
                "items": {k: structure_skeleton(v) for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        return {"!kind": "tuple" if isinstance(tree, tuple) else "list",
                "items": [structure_skeleton(v) for v in tree]}
    return {"!kind": "leaf"}


def build_from_skeleton(skel: Any, leaves: Dict[str, Any], path: str = "") -> Any:
    kind = skel["!kind"]
    if kind == "dict":
        return {k: build_from_skeleton(v, leaves, f"{path}{k}/")
                for k, v in skel["items"].items()}
    if kind in ("tuple", "list"):
        seq = [build_from_skeleton(v, leaves, f"{path}{i}/")
               for i, v in enumerate(skel["items"])]
        return tuple(seq) if kind == "tuple" else seq
    return leaves[path[:-1]]


# ---------------------------------------------------------------------------
# Manifest
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ChunkInfo:
    offset: Tuple[int, ...]
    shape: Tuple[int, ...]
    key: str                          # store key of the chunk object
    nbytes: int                       # encoded size
    hash: Optional[str] = None        # blake2b digest of encoded bytes (v2)


@dataclasses.dataclass
class PreEncodedLeaf:
    """Staging-form leaf whose shards were already encoded on device.

    Appears as an (unregistered, hence atomic) pytree leaf inside a
    snapshot produced by ``TrainerApp.snapshot_async`` with a lossy swap
    codec: ``chunks`` carries ``(offset, shape, PreEncodedChunk)`` triples
    in place of host ndarrays. ``writer._stage`` passes these straight to
    the upload pipeline; the manifest entry (shape/dtype/kind) is
    indistinguishable from a host-encoded leaf, so restore needs no new
    code path.
    """
    shape: Tuple[int, ...]
    dtype: str
    chunks: List[Tuple[Tuple[int, ...], Tuple[int, ...], Any]]
    kind: str = "array"


@dataclasses.dataclass
class LeafInfo:
    name: str
    shape: Tuple[int, ...]
    dtype: str
    kind: str                         # "array" | "scalar"
    chunks: List[ChunkInfo]


@dataclasses.dataclass
class Manifest:
    step: int
    codec: str
    leaves: Dict[str, LeafInfo]
    skeleton: Any
    metadata: Dict[str, Any]
    version: int = FORMAT_VERSION

    def to_json(self) -> str:
        def enc(o):
            if dataclasses.is_dataclass(o):
                return dataclasses.asdict(o)
            raise TypeError(o)
        return json.dumps(dataclasses.asdict(self), default=enc)

    def chunk_refs(self) -> Dict[str, int]:
        """store key -> number of references from this manifest."""
        refs: Dict[str, int] = {}
        for li in self.leaves.values():
            for c in li.chunks:
                refs[c.key] = refs.get(c.key, 0) + 1
        return refs

    @staticmethod
    def from_json(s: str) -> "Manifest":
        d = json.loads(s)
        leaves = {
            name: LeafInfo(
                name=li["name"], shape=tuple(li["shape"]), dtype=li["dtype"],
                kind=li["kind"],
                chunks=[ChunkInfo(tuple(c["offset"]), tuple(c["shape"]),
                                  c["key"], c["nbytes"], c.get("hash"))
                        for c in li["chunks"]])
            for name, li in d["leaves"].items()
        }
        return Manifest(step=d["step"], codec=d["codec"], leaves=leaves,
                        skeleton=d["skeleton"], metadata=d["metadata"],
                        version=d.get("version", 1))


def step_prefix(prefix: str, step: int) -> str:
    return f"{prefix}/step_{step:010d}"


def chunk_key(prefix: str, step: int, leaf: str,
              offset: Sequence[int]) -> str:
    """Format-v1 (step-private) chunk key; kept for full / legacy saves."""
    off = "o" + "_".join(str(int(o)) for o in offset) if offset else "o0"
    return f"{step_prefix(prefix, step)}/chunks/{leaf}::{off}"


def chunk_digest(data: bytes) -> str:
    """Content address of an encoded chunk (hex blake2b-160)."""
    return hashlib.blake2b(data, digest_size=20).hexdigest()


def cas_prefix(prefix: str) -> str:
    return f"{prefix}/{CAS_DIR}/"


def cas_key(prefix: str, digest: str) -> str:
    """Format-v2 content-addressed chunk key (shared across steps)."""
    return f"{cas_prefix(prefix)}{digest}"


# ---------------------------------------------------------------------------
# Shard enumeration
# ---------------------------------------------------------------------------

def _index_to_offset_shape(index: Tuple[slice, ...],
                           shape: Tuple[int, ...]) -> Tuple[Tuple[int, ...],
                                                            Tuple[int, ...]]:
    offs, shp = [], []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        offs.append(start)
        shp.append(stop - start)
    return tuple(offs), tuple(shp)


def local_shards(arr) -> List[Tuple[Tuple[int, ...], Tuple[int, ...],
                                    np.ndarray]]:
    """Unique addressable shards of a jax.Array (replicas deduped).

    Returns [(offset, shape, host_ndarray)]. Each device-to-host copy is
    one ``ckpt/d2h`` span.
    """
    if not isinstance(arr, jax.Array):
        a = np.asarray(arr)
        return [((0,) * a.ndim, a.shape, a)]
    out = []
    seen = set()
    for sh in arr.addressable_shards:
        off, shp = _index_to_offset_shape(
            tuple(sh.index) if sh.index else (slice(None),) * arr.ndim,
            arr.shape)
        if off in seen:
            continue
        seen.add(off)
        with tracer().span("ckpt/d2h", cat="ckpt",
                           args={"nbytes": sh.data.nbytes, "shape": shp}):
            host = np.asarray(sh.data)
        out.append((off, shp, host))
    return out

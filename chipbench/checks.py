"""Comparison helpers, copied from the repo's chip smoke check so that the
yardstick does not change when that script does."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

# Counters the control plane bumps instead of raising; any rise is a failure.
HEALTH_COUNTERS = ("appmgr.daemon_errors", "appmgr.op_errors",
                   "serve.decode_failures", "serve.stop_timeouts",
                   "trainer.step_failures", "ckpt.failed_saves")


def path_items(tree: Any) -> Dict[str, Any]:
    """'a/b/c' -> leaf for every leaf of a pytree."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = x
    return out


def host_tree(tree: Any) -> Dict[str, np.ndarray]:
    """name -> host ndarray for every array leaf."""
    return {n: np.asarray(jax.device_get(x))
            for n, x in path_items(tree).items() if hasattr(x, "shape")}


def trees_equal(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> bool:
    return a.keys() == b.keys() and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a)


def _bits(x):
    x = jnp.asarray(x)
    if x.dtype.itemsize == 1 or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[x.dtype.itemsize])


def leaves_differing(a: Any, b: Any) -> int:
    """Leaves of two pytrees that differ in path, shape, dtype or any bit,
    compared on the device. Non-array leaves compare by value."""
    pa, pb = path_items(a), path_items(b)
    if pa.keys() != pb.keys():
        return len(pa.keys() ^ pb.keys()) or 1
    bad = 0
    for k in pa:
        x, y = pa[k], pb[k]
        if not hasattr(x, "shape") or not hasattr(y, "shape"):
            bad += int(not np.array_equal(np.asarray(x), np.asarray(y)))
            continue
        if x.shape != y.shape or x.dtype != y.dtype:
            bad += 1
            continue
        bad += int(not bool(jnp.array_equal(_bits(x), _bits(y))))
    return bad


class Health:
    """Error counters as deltas from construction."""

    def __init__(self, registry):
        self._reg = registry
        self._base = {n: registry.value(n, 0.0) for n in HEALTH_COUNTERS}

    def rises(self) -> Dict[str, float]:
        out = {}
        for n, b in self._base.items():
            v = self._reg.value(n, 0.0) - b
            if v:
                out[n] = v
        return out

"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the yardstick's counts: the kernels' rooflines and the steps'
FLOP shares divide them by measured time, so they count what the
computation needs, never what an implementation happens to move.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def layer_params(cfg: Dict[str, Any]) -> int:
    """Matmul weights plus the two norms of one decoder layer."""
    s = dims(cfg)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])
    return attn + 3 * s["d"] * s["f"] + 2 * s["d"]


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters held, at the configuration's vocabulary (unpadded)."""
    s = dims(cfg)
    embed = s["V"] * s["d"] * (1 if s["tied"] else 2)
    return s["L"] * layer_params(cfg) + embed + s["d"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that take part in a matmul per token: every layer's
    projections and the head (an embedding lookup is no matmul)."""
    s = dims(cfg)
    per_layer = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"]) \
        + 3 * s["d"] * s["f"]
    return s["L"] * per_layer + s["V"] * s["d"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one trained token: 6 x matmul params, plus causal-free
    attention at the full S x S the step computes (QK^T and PV, forward
    and backward: 3 x 2 x 2 x S x h x hd per layer). Recompute is not
    counted."""
    s = dims(cfg)
    attn = 12.0 * s["L"] * seq_len * s["h"] * s["hd"]
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    s = dims(cfg)
    return 2 * s["L"] * s["kv"] * s["hd"] * itemsize


def decode_step_cost(cfg: Dict[str, Any], batch: int, filled: float,
                     itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and needed bytes of one decode step of ``batch`` rows whose
    caches hold ``filled`` positions on average: every weight read once,
    plus the filled cache positions (not the padded cache)."""
    s = dims(cfg)
    flops = batch * (2.0 * matmul_params(cfg)
                     + 4.0 * s["L"] * filled * s["h"] * s["hd"])
    weight_bytes = param_count(cfg) * itemsize
    cache_bytes = batch * filled * kv_bytes_per_token(cfg, itemsize)
    return {"flops": flops, "bytes": weight_bytes + cache_bytes}


def qsnap_encode_bytes(leaves) -> int:
    """Bytes the int8 encode needs: each float leaf read once in its stored
    dtype, plus its int8 codes and one f32 scale per 256 elements."""
    import jax.numpy as jnp
    total = 0
    for shape, dtype in leaves:
        n = int(np.prod(shape))
        blocks = -(-n // 256)
        total += n * jnp.dtype(dtype).itemsize + blocks * 256 + blocks * 4
    return total


def tree_bytes(tree: Any) -> int:
    """Bytes of every array leaf of a pytree (copied from the repo's
    checkpoint benchmarks)."""
    import jax
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                   if hasattr(x, "nbytes")))

"""Operations and bytes a Jamba hybrid decoder needs, computed from the
configuration file alone (``config.json`` keys), as ``counts`` computes
them for the dense decoder: what the computation needs, never what an
implementation happens to move.

A period of ``attn_layer_period`` layers holds one attention layer (at
``attn_layer_offset``) and Mamba-1 mixers elsewhere; every layer has a
SwiGLU MLP (``num_experts`` 1). Weights are counted in bf16, the
recurrent state ``h`` in f32 and the conv tail and the K/V rows in bf16,
as the configuration file states.
"""
from __future__ import annotations

from typing import Any, Dict

# elementwise operations of the selective recurrence per state element and
# token: the decay's argument dt*A, the decay times the state, the input
# term's product with B, their sum, and the read-out's multiply and add
SCAN_OPS = 6
H_BYTES, CONV_BYTES, KV_BYTES, W_BYTES = 4, 2, 2, 2


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    L, period = cfg["num_hidden_layers"], cfg["attn_layer_period"]
    if cfg.get("num_experts", 1) != 1:
        raise ValueError(f"{cfg['name']}: expert layers are not counted")
    n_attn = sum(1 for i in range(L)
                 if i % period == cfg["attn_layer_offset"])
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "L": L, "n_attn": n_attn,
            "n_mamba": L - n_attn, "di": cfg["mamba_expand"] * d,
            "N": cfg["mamba_d_state"], "W": cfg["mamba_d_conv"],
            "R": cfg["mamba_dt_rank"], "V": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def _mamba_matmul(s) -> int:
    """in_proj, x_proj, dt_proj and out_proj of one Mamba layer."""
    d, di, N, R = s["d"], s["di"], s["N"], s["R"]
    return 2 * d * di + di * (R + 2 * N) + R * di + di * d


def _attn_matmul(s) -> int:
    return s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that take part in a matmul per token: every mixer's and
    MLP's projections and the head (an embedding lookup is no matmul)."""
    s = dims(cfg)
    return (s["n_mamba"] * _mamba_matmul(s) + s["n_attn"] * _attn_matmul(s)
            + s["L"] * 3 * s["d"] * s["f"] + s["V"] * s["d"])


def param_count(cfg: Dict[str, Any]) -> int:
    """Every weight held: matmul weights, the conv, dt bias, A, D, every
    norm, the embedding (once if tied) and the final norm."""
    s = dims(cfg)
    d, di, N, R, W = s["d"], s["di"], s["N"], s["R"], s["W"]
    mamba = _mamba_matmul(s) + W * di + di + di + di * N + di \
        + d + R + 2 * N                 # conv w, conv b, dt bias, A, D, norms
    attn = _attn_matmul(s) + d
    mlp = 3 * d * s["f"] + d
    embed = s["V"] * d * (1 if s["tied"] else 2)
    return (s["n_mamba"] * mamba + s["n_attn"] * attn + s["L"] * mlp
            + embed + d)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    s = dims(cfg)
    return 2 * s["n_attn"] * s["kv"] * s["hd"] * KV_BYTES


def state_bytes_per_row(cfg: Dict[str, Any]) -> int:
    """Recurrent state of one sequence: f32 ``h`` [N, di] and the bf16
    conv tail [W-1, di] of every Mamba layer."""
    s = dims(cfg)
    return s["n_mamba"] * (s["N"] * s["di"] * H_BYTES
                           + (s["W"] - 1) * s["di"] * CONV_BYTES)


def _scan_flops(s) -> float:
    """The recurrence and the depthwise conv of every Mamba layer, per
    token."""
    return s["n_mamba"] * (SCAN_OPS * s["N"] * s["di"]
                           + 2.0 * s["W"] * s["di"])


def decode_step_cost(cfg: Dict[str, Any], batch: int,
                     filled: float) -> Dict[str, float]:
    """FLOPs and needed bytes of one decode step of ``batch`` rows whose
    caches hold ``filled`` positions on average: every weight read once,
    the filled K/V positions read, and every row's recurrent state read
    and written."""
    s = dims(cfg)
    flops = batch * (2.0 * matmul_params(cfg) + _scan_flops(s)
                     + 4.0 * s["n_attn"] * filled * s["h"] * s["hd"])
    need = (param_count(cfg) * W_BYTES
            + batch * filled * kv_bytes_per_token(cfg)
            + 2 * batch * state_bytes_per_row(cfg))
    return {"flops": flops, "bytes": float(need)}


def prefill_flops(cfg: Dict[str, Any], rows: int, prompt_len: int) -> float:
    """FLOPs of prefilling ``rows`` prompts of ``prompt_len`` tokens: the
    projections of every position (the head only at the last), causal
    attention (QK^T and PV over the positions at or before each query)
    and the recurrence."""
    s = dims(cfg)
    S = prompt_len
    per_row = (2.0 * (matmul_params(cfg) - s["V"] * s["d"]) * S
               + 2.0 * s["V"] * s["d"]
               + 4.0 * s["n_attn"] * s["h"] * s["hd"] * S * (S + 1) / 2
               + _scan_flops(s) * S)
    return rows * per_row

"""The bridge to the system under test: puts ``src`` on the path and turns
a configuration file into the program's ``ArchConfig``. Everything else the
benchmark takes from the program goes through the drivers."""
from __future__ import annotations

import sys
from typing import Any, Dict

from chipbench.configs import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def arch(cfg: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU decoders are driven")
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim"), mlp_act="swiglu",
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)))

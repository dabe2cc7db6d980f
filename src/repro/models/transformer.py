"""Composable decoder stacks built from block templates.

An architecture is compiled (at trace time) into a *group program*: an
ordered list of ``Block`` templates covering one period of the arch's layer
pattern (e.g. jamba: ``[attn+mlp, mamba+moe, mamba+mlp, ...]`` — 8 layers;
gemma3: 5 sliding-window + 1 global). The full stack is a ``jax.lax.scan``
over ``n_groups`` stacked copies of the group params, so compile time is
independent of depth (96-layer nemotron lowers as fast as 12-layer xlstm).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.sharding.specs import constrain

Params = Any


@dataclasses.dataclass(frozen=True)
class Block:
    kind: str            # attn | cross_attn | mlp | moe | mamba | mlstm | slstm
    name: str
    spec: Any


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def build_group(cfg: ArchConfig) -> Tuple[List[Block], int]:
    """One period of the layer pattern + how many times it repeats."""
    if cfg.xlstm is not None:
        gs = cfg.xlstm.slstm_every
        assert cfg.n_layers % gs == 0
        blocks: List[Block] = []
        for j in range(gs):
            if j == gs - 1:
                blocks.append(Block("slstm", f"l{j}_slstm",
                                    X.SLSTMSpec(cfg.d_model, cfg.n_heads,
                                                cfg.norm_eps)))
            else:
                blocks.append(Block("mlstm", f"l{j}_mlstm",
                                    X.MLSTMSpec(cfg.d_model, cfg.n_heads,
                                                cfg.xlstm, cfg.norm_eps)))
        return blocks, cfg.n_layers // gs

    gs = 1
    if cfg.attn_pattern == "local_global":
        gs = _lcm(gs, cfg.local_global_ratio + 1)
    if cfg.attn_every > 1:
        gs = _lcm(gs, cfg.attn_every)
    if cfg.moe is not None:
        gs = _lcm(gs, cfg.moe.every)
    assert cfg.n_layers % gs == 0, (cfg.name, cfg.n_layers, gs)

    blocks = []
    for j in range(gs):
        # --- token mixer ------------------------------------------------
        if cfg.attn_every > 1 and (j % cfg.attn_every) != 0:
            blocks.append(Block("mamba", f"l{j}_mamba",
                                S.MambaSpec(cfg.d_model, cfg.ssm, cfg.norm_eps)))
        else:
            window = None
            if cfg.attn_pattern == "local_global":
                r = cfg.local_global_ratio
                if (j % (r + 1)) != r:        # last of each sub-period = global
                    window = cfg.local_window
            blocks.append(Block("attn", f"l{j}_attn", L.AttnSpec(
                cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.rope_theta, cfg.norm_eps, window=window)))
            if cfg.encoder is not None:
                blocks.append(Block("cross_attn", f"l{j}_xattn", L.AttnSpec(
                    cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                    cfg.rope_theta, cfg.norm_eps, cross=True, use_rope=False)))
        # --- channel mixer ------------------------------------------------
        if cfg.moe is not None and (j % cfg.moe.every) == cfg.moe.every - 1:
            blocks.append(Block("moe", f"l{j}_moe", M.MoESpec(
                cfg.d_model, cfg.moe, cfg.mlp_act, cfg.norm_eps,
                d_ff_shared=cfg.d_ff if cfg.moe.shared_expert else 0)))
        elif cfg.d_ff > 0:
            blocks.append(Block("mlp", f"l{j}_mlp", L.MLPSpec(
                cfg.d_model, cfg.d_ff, cfg.mlp_act, cfg.norm_eps)))
    return blocks, cfg.n_layers // gs


def build_encoder_group(cfg: ArchConfig) -> Tuple[List[Block], int]:
    e = cfg.encoder
    blocks = [
        Block("attn", "enc_attn", L.AttnSpec(
            cfg.d_model, e.n_heads, e.n_kv_heads, cfg.head_dim,
            cfg.rope_theta, cfg.norm_eps, causal=False)),
        Block("mlp", "enc_mlp", L.MLPSpec(cfg.d_model, e.d_ff, cfg.mlp_act,
                                          cfg.norm_eps)),
    ]
    return blocks, e.n_layers


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(b: L.ParamBuilder, blk: Block) -> None:
    if blk.kind in ("attn", "cross_attn"):
        L.attn_init(b, blk.spec)
    elif blk.kind == "mlp":
        L.mlp_init(b, blk.spec)
    elif blk.kind == "moe":
        M.moe_init(b, blk.spec)
    elif blk.kind == "mamba":
        S.mamba_init(b, blk.spec)
    elif blk.kind == "mlstm":
        X.mlstm_init(b, blk.spec)
    elif blk.kind == "slstm":
        X.slstm_init(b, blk.spec)
    else:
        raise ValueError(blk.kind)


def init_stack(key: jax.Array, blocks: List[Block], n_groups: int,
               dtype) -> Params:
    """Stacked params: every leaf gets a leading [n_groups] dim."""
    def one_group(k):
        b = L.ParamBuilder(k, dtype)
        for blk in blocks:
            b.sub(blk.name, lambda bb, blk=blk: _init_block(bb, blk))
        return b.params

    return jax.vmap(one_group)(jax.random.split(key, n_groups))


def stack_dims(blocks: List[Block]) -> Any:
    """Logical-dims tree matching ``init_stack`` (computed abstractly —
    no full-size allocation; safe for 340B configs)."""
    holder: Dict[str, Any] = {}

    def capture():
        db: Dict[str, Any] = {}
        outs = []
        for blk in blocks:
            b2 = L.ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
            _init_block(b2, blk)
            db[blk.name] = b2.dims
            outs.append(b2.params)
        holder["dims"] = db
        return outs

    jax.eval_shape(capture)
    return jax.tree.map(lambda d: ("layers",) + tuple(d), holder["dims"],
                        is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Forward (training)
# ---------------------------------------------------------------------------

def stack_forward(params_stack: Params, blocks: List[Block], x: jax.Array,
                  positions: jax.Array, *, enc_out: Optional[jax.Array] = None,
                  remat: bool = True, unroll: bool = False,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Scan the group program over the stacked params. Returns (x, moe_aux).

    ``unroll=True`` replaces the scan with a Python loop — used by the
    dry-run's cost probes (XLA cost_analysis counts while bodies once).
    """

    def body(carry, p_g):
        x, aux = carry
        for blk in blocks:
            p = p_g[blk.name]
            if blk.kind == "attn":
                x = L.attn_apply(p, blk.spec, x, positions=positions)
            elif blk.kind == "cross_attn":
                mem = L.cross_attn_memory(p, blk.spec, enc_out)
                x = L.attn_apply(p, blk.spec, x, positions=positions,
                                 memory=mem)
            elif blk.kind == "mlp":
                x = L.mlp_apply(p, blk.spec, x)
            elif blk.kind == "moe":
                x, a = M.moe_apply(p, blk.spec, x)
                aux = aux + a
            elif blk.kind == "mamba":
                x = S.mamba_apply(p, blk.spec, x)
            elif blk.kind == "mlstm":
                x = X.mlstm_apply(p, blk.spec, x)
            elif blk.kind == "slstm":
                x = X.slstm_apply(p, blk.spec, x)
            x = constrain(x, ("dp", "sp", None))
        return (x, aux), None

    if remat == "save_moe":
        # selective remat: keep the MoE boundary tensors so the backward
        # pass does not re-execute the dp<->ep reshard collectives
        policy = jax.checkpoint_policies.save_only_these_names(
            "moe_dispatch", "moe_expert_out")
        body_fn = jax.checkpoint(body, policy=policy)
    elif remat:
        body_fn = jax.checkpoint(body)
    else:
        body_fn = body
    carry = (x, jnp.zeros((), jnp.float32))
    if unroll:
        n = jax.tree.leaves(params_stack)[0].shape[0]
        for i in range(n):
            p_g = jax.tree.map(lambda t, i=i: t[i], params_stack)
            carry, _ = body_fn(carry, p_g)
        return carry
    (x, aux), _ = jax.lax.scan(body_fn, carry, params_stack)
    return x, aux


# ---------------------------------------------------------------------------
# Prefill (returns decode caches) and decode
# ---------------------------------------------------------------------------

def stack_prefill(params_stack: Params, blocks: List[Block], x: jax.Array,
                  positions: jax.Array, *,
                  enc_out: Optional[jax.Array] = None,
                  cache_len: Optional[int] = None, unroll: bool = False,
                  ) -> Tuple[jax.Array, Params]:
    """Forward + per-layer cache construction. cache_len pads KV caches."""

    def body(x, p_g):
        caches: Dict[str, Any] = {}
        for blk in blocks:
            p = p_g[blk.name]
            if blk.kind == "attn":
                x, c = L.attn_prefill(p, blk.spec, x, positions=positions)
                if cache_len is not None and cache_len > c["k"].shape[1]:
                    pad = cache_len - c["k"].shape[1]
                    c = {kk: jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for kk, vv in c.items()}
                caches[blk.name] = c
            elif blk.kind == "cross_attn":
                mk, mv = L.cross_attn_memory(p, blk.spec, enc_out)
                x = L.attn_apply(p, blk.spec, x, positions=positions,
                                 memory=(mk, mv))
                caches[blk.name] = {"mk": mk, "mv": mv}
            elif blk.kind == "mlp":
                x = L.mlp_apply(p, blk.spec, x)
            elif blk.kind == "moe":
                x, _ = M.moe_apply(p, blk.spec, x)
            elif blk.kind == "mamba":
                x, c = S.mamba_prefill(p, blk.spec, x)
                caches[blk.name] = c
            elif blk.kind == "mlstm":
                x, c = X.mlstm_prefill(p, blk.spec, x)
                caches[blk.name] = c
            elif blk.kind == "slstm":
                x, c = X.slstm_prefill(p, blk.spec, x)
                caches[blk.name] = c
            x = constrain(x, ("dp", "sp", None))
        return x, caches

    if unroll:
        n = jax.tree.leaves(params_stack)[0].shape[0]
        caches = []
        for i in range(n):
            p_g = jax.tree.map(lambda t, i=i: t[i], params_stack)
            x, c = body(x, p_g)
            caches.append(c)
        cache_stack = jax.tree.map(lambda *ts: jnp.stack(ts), *caches)
        return x, cache_stack
    return jax.lax.scan(body, x, params_stack)


_RECURRENT_DECODE = {"mamba": S.mamba_decode, "mlstm": X.mlstm_decode,
                     "slstm": X.slstm_decode}


def stack_decode(params_stack: Params, blocks: List[Block], x: jax.Array,
                 cache_stack: Params, pos: jax.Array, *,
                 unroll: bool = False) -> Tuple[jax.Array, Params]:
    """One-token decode through the stack. x: [B,1,d].

    The stacked cache rides in the carry, not in the scan's xs/ys: each
    layer writes its own row (attention: position ``pos``; recurrent
    blocks: their state) into the whole buffer, so a donated cache is
    updated in place and no layer's slice is copied out and back.
    """

    def body(carry, inp):
        x, cache = carry
        i, p_g = inp
        cache = dict(cache)
        for blk in blocks:
            p = p_g[blk.name]
            if blk.kind == "attn":
                x, cache[blk.name] = L.attn_decode(
                    p, blk.spec, x, cache[blk.name], pos, i)
            elif blk.kind == "cross_attn":
                c = cache[blk.name]
                mem = (c["mk"][i], c["mv"][i])
                x = L.cross_attn_decode(p, blk.spec, x, mem)
            elif blk.kind == "mlp":
                x = L.mlp_apply(p, blk.spec, x)
            elif blk.kind == "moe":
                x, _ = M.moe_apply(p, blk.spec, x)
            elif blk.kind in _RECURRENT_DECODE:
                c = cache[blk.name]
                x, c_i = _RECURRENT_DECODE[blk.kind](
                    p, blk.spec, x, jax.tree.map(lambda t: t[i], c))
                cache[blk.name] = jax.tree.map(
                    lambda t, u: jax.lax.dynamic_update_index_in_dim(
                        t, u.astype(t.dtype), i, 0), c, c_i)
        return (x, cache), None

    carry = (x, cache_stack)
    n = jax.tree.leaves(params_stack)[0].shape[0]
    if unroll:
        for i in range(n):
            p_g = jax.tree.map(lambda t, i=i: t[i], params_stack)
            carry, _ = body(carry, (i, p_g))
        return carry
    carry, _ = jax.lax.scan(body, carry, (jnp.arange(n), params_stack))
    return carry


# ---------------------------------------------------------------------------
# Cache construction + logical dims (for sharding)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, blocks: List[Block], n_groups: int,
               batch: int, cache_len: int, dtype,
               enc_len: int = 0) -> Params:
    """Zero-initialized decode cache (capacity ``cache_len``)."""
    def one(blk: Block):
        if blk.kind == "attn":
            sp = blk.spec
            shape = (n_groups, batch, cache_len, sp.n_kv_heads, sp.head_dim)
            return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if blk.kind == "cross_attn":
            sp = blk.spec
            shape = (n_groups, batch, enc_len, sp.n_kv_heads, sp.head_dim)
            return {"mk": jnp.zeros(shape, dtype), "mv": jnp.zeros(shape, dtype)}
        if blk.kind == "mamba":
            c = S.mamba_cache_init(blk.spec, batch, dtype)
        elif blk.kind == "mlstm":
            c = X.mlstm_cache_init(blk.spec, batch, dtype)
        elif blk.kind == "slstm":
            c = X.slstm_cache_init(blk.spec, batch, dtype)
        else:
            return None
        return jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_groups,) + t.shape), c)

    caches = {blk.name: one(blk) for blk in blocks}
    return {k: v for k, v in caches.items() if v is not None}


def cache_dims(blocks: List[Block]) -> Any:
    """Logical dims tree matching ``init_cache`` output."""
    out: Dict[str, Any] = {}
    for blk in blocks:
        if blk.kind in ("attn",):
            d = ("layers", "batch", "kvseq", "kv_heads", "head_dim")
            out[blk.name] = {"k": d, "v": d}
        elif blk.kind == "cross_attn":
            d = ("layers", "batch", "kvseq", "kv_heads", "head_dim")
            out[blk.name] = {"mk": d, "mv": d}
        elif blk.kind == "mamba":
            out[blk.name] = {"h": ("layers", "batch", "ssm_inner", None),
                             "conv": ("layers", "batch", None, "ssm_inner")}
        elif blk.kind == "mlstm":
            out[blk.name] = {"C": ("layers", "batch", None, "head_dim", None),
                             "n": ("layers", "batch", None, "head_dim"),
                             "conv": ("layers", "batch", None, "xl_inner")}
        elif blk.kind == "slstm":
            d = ("layers", "batch", "embed_nt")
            out[blk.name] = {k: d for k in ("c", "n", "h", "m")}
    return out

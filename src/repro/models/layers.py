"""Core model layers: norms, RoPE, GQA attention, MLP variants.

Everything is pure jnp, and XLA lowers it directly on every backend:
attention is plain XLA today, on the TPU as on the CPU. The prefill runs
it over blocks of queries (``attention_blocked``), so its scores never
span the whole prompt twice. The Pallas attention kernels in
``repro.kernels`` are tested against their jnp oracles but no model
calls them.

Parameters are plain pytrees of jnp arrays. Each builder also records the
*logical dims* of every leaf (e.g. ``("embed", "q_dim")``) in a parallel
pytree — ``repro.sharding.specs`` maps logical dims to mesh axes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.sharding.specs import active_axis_size, constrain, sharding_active

Params = Any
Dims = Any


class ParamBuilder:
    """Collects (param, logical-dims) pairs with a split PRNG key."""

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self._key = key
        self.dtype = dtype
        self.params: Dict[str, Any] = {}
        self.dims: Dict[str, Any] = {}

    def _next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def add(self, name: str, shape: Tuple[int, ...], dims: Tuple[Optional[str], ...],
            init: str = "normal", scale: Optional[float] = None) -> None:
        assert len(shape) == len(dims), (name, shape, dims)
        if init == "zeros":
            p = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            p = jnp.ones(shape, self.dtype)
        elif init == "s4d_real":
            # Mamba's A_log: log(1..N) along the last (state) axis
            n = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
            p = jnp.broadcast_to(jnp.log(n), shape).astype(self.dtype)
        elif init == "mamba_dt":
            # Mamba's dt bias: softplus(bias) spans [1e-3, 1e-1], here
            # log-spaced over the channels (the paper draws it log-uniform)
            dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1),
                                      shape[-1], dtype=jnp.float32))
            p = jnp.broadcast_to(dt + jnp.log(-jnp.expm1(-dt)),
                                 shape).astype(self.dtype)
        else:
            if scale is None:
                fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
                scale = 1.0 / math.sqrt(fan_in)
            p = (jax.random.normal(self._next(), shape, jnp.float32)
                 * scale).astype(self.dtype)
        self.params[name] = p
        self.dims[name] = dims

    def sub(self, name: str, builder_fn) -> None:
        b = ParamBuilder(self._next(), self.dtype)
        builder_fn(b)
        self.params[name] = b.params
        self.dims[name] = b.dims

    def build(self) -> Tuple[Params, Dims]:
        return self.params, self.dims


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm, dtype-preserving in BOTH directions.

    Plain autodiff of an f32-variance rmsnorm promotes the residual-stream
    cotangent to f32, which then rides through every backward dot and turns
    the per-layer dx all-reduces into f32 (2x bytes) — measured in §Perf
    iteration C. The custom VJP keeps [B,S,d] tangents in the compute
    dtype; only the row reductions run in f32.
    """
    return _rms_fwd(x, w, eps)[0]


def _rms_fwd(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32)
    r = jax.lax.rsqrt(var + eps)                      # f32 [..., 1]
    y = x * r.astype(x.dtype) * w
    return y, (x, w, r)


def _rms_bwd(eps, res, dy):
    x, w, r = res
    dt = x.dtype
    d = x.shape[-1]
    s = dy * w                                        # compute dtype
    dot = jnp.sum(x * s, axis=-1, keepdims=True,
                  dtype=jnp.float32)                  # f32 [..., 1]
    coef = (r ** 3 * dot / d).astype(dt)              # [..., 1]
    dx = s * r.astype(dt) - x * coef
    dw_full = dy * x * r.astype(dt)
    dw = jnp.sum(dw_full.reshape(-1, d), axis=0,
                 dtype=jnp.float32).astype(w.dtype)
    return dx, dw


rmsnorm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: [..., S] (broadcastable).

    Angles are computed in f32 (tiny [S,hd/2] tables); the rotation itself
    runs in the compute dtype — no full-tensor f32 round-trip.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # [hd/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles).astype(x.dtype)                   # [...,S,1,hd/2]
    sin = jnp.sin(angles).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


# ---------------------------------------------------------------------------
# Attention (reference path). Grouped-query form: KV heads are never
# materialized q_per_kv times.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: Optional[int] = None,
                  q_positions: Optional[jax.Array] = None,
                  kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """q: [B,S,Hq,hd]; k,v: [B,T,Hkv,hd] -> [B,S,Hq,hd].

    ``window`` (if set) restricts attention to the last ``window`` keys
    relative to each query (sliding-window / local attention).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, S, Hkv, g, hd)
    # scores stay in the compute dtype; softmax reductions accumulate f32
    # (§Perf iteration B — the f32 [S,T] materializations dominated bytes)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) / math.sqrt(hd)

    if q_positions is None:
        q_positions = jnp.arange(S)
    if kv_positions is None:
        kv_positions = jnp.arange(T)
    rel = q_positions[:, None] - kv_positions[None, :]       # [S,T]
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= rel >= 0
    if window is not None:
        mask &= rel < window
    neg = jnp.asarray(NEG_INF, scores.dtype)
    scores = jnp.where(mask[None, None, None], scores, neg)
    m = jax.lax.stop_gradient(
        jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m)                                  # compute dtype
    denom = jnp.sum(p, axis=-1, keepdims=True,
                    dtype=jnp.float32).astype(p.dtype)
    probs = p / jnp.maximum(denom, jnp.asarray(1e-30, p.dtype))
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, Hq, hd)


# queries per block of the prefill's attention
Q_BLOCK = 512


def attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      block: int = Q_BLOCK) -> jax.Array:
    """``attention_ref`` over queries and keys at positions 0..S-1, one
    block of ``block`` queries at a time (a ``lax.map``): the scores held
    at once are [B,Hkv,g,block,T], not [B,Hkv,g,S,T]. Each query's row is
    computed as ``attention_ref`` computes it."""
    S, T = q.shape[1], k.shape[1]
    if S <= block:
        return attention_ref(q, k, v, causal=causal, window=window)
    nb = -(-S // block)
    qp = jnp.pad(q, ((0, 0), (0, nb * block - S), (0, 0), (0, 0)))
    kv_positions = jnp.arange(T)

    def one(i):
        lo = i * block
        qb = jax.lax.dynamic_slice_in_dim(qp, lo, block, axis=1)
        return attention_ref(qb, k, v, causal=causal, window=window,
                             q_positions=lo + jnp.arange(block),
                             kv_positions=kv_positions)

    out = jax.lax.map(one, jnp.arange(nb))              # [nb,B,block,H,hd]
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape[0], nb * block,
                                          *q.shape[2:])
    return out[:, :S]


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    window: Optional[int] = None        # sliding window, None = full
    causal: bool = True
    cross: bool = False                 # cross-attention (enc-dec)
    use_rope: bool = True


def attn_init(b: ParamBuilder, spec: AttnSpec) -> None:
    d, H, Hkv, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    b.add("norm", (d,), ("embed_nt",), init="ones")
    b.add("wq", (d, H, hd), ("embed", "heads", "head_dim"))
    b.add("wk", (d, Hkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wv", (d, Hkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wo", (H, hd, d), ("heads", "head_dim", "embed"),
          scale=1.0 / math.sqrt(H * hd))


def _proj(x: jax.Array, w: jax.Array) -> jax.Array:
    """[B,S,d] @ [d,H,hd] -> [B,S,H,hd]."""
    return jnp.einsum("bsd,dhk->bshk", x, w)


def _out_proj(o: jax.Array, w: jax.Array) -> jax.Array:
    """[B,S,H,hd] @ [H,hd,d] -> [B,S,d]."""
    return jnp.einsum("bshk,hkd->bsd", o, w)


def attn_qkv(p: Params, spec: AttnSpec, x: jax.Array,
             positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    h = rmsnorm(x, p["norm"], spec.norm_eps)
    q, k, v = _proj(h, p["wq"]), _proj(h, p["wk"]), _proj(h, p["wv"])
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def attn_apply(p: Params, spec: AttnSpec, x: jax.Array, *,
               positions: jax.Array,
               memory: Optional[Tuple[jax.Array, jax.Array]] = None) -> jax.Array:
    """Self- (or cross-, if ``memory``) attention with residual."""
    if spec.cross:
        assert memory is not None
        mk, mv = memory
        h = rmsnorm(x, p["norm"], spec.norm_eps)
        q = _proj(h, p["wq"])
        out = attention_ref(q, mk, mv, causal=False)
    else:
        q, k, v = attn_qkv(p, spec, x, positions)
        out = attention_ref(q, k, v, causal=spec.causal, window=spec.window,
                            q_positions=positions, kv_positions=positions)
    return x + _out_proj(out, p["wo"])


def attn_prefill(p: Params, spec: AttnSpec, x: jax.Array, *,
                 positions: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Like attn_apply but also returns the KV cache. The prompt sits at
    positions 0..S-1; its attention runs over blocks of queries."""
    q, k, v = attn_qkv(p, spec, x, positions)
    out = attention_blocked(q, k, v, causal=spec.causal, window=spec.window)
    return x + _out_proj(out, p["wo"]), {"k": k, "v": v}


# positions rewritten around the decoded one (see _write_row)
_ROW_CHUNK = 8


def _write_row(buf: jax.Array, row: jax.Array, layer: jax.Array,
               pos: jax.Array) -> jax.Array:
    """Write ``row`` [B,1,Hkv,hd] at (layer, :, pos) of the stacked cache
    ``buf`` [L,B,S,Hkv,hd].

    The write rewrites the aligned chunk of ``_ROW_CHUNK`` positions that
    holds ``pos``, the others with the values they already hold. A
    one-position update leads XLA's TPU layout assignment to store the
    carried cache with the written row contiguous, which pads a small
    minor dim (a sharded or 64-wide head_dim) up to 128 lanes and
    brackets the layer loop with whole-cache relayouts; a chunk keeps
    the layout the cache was given.
    """
    w = math.gcd(buf.shape[2], _ROW_CHUNK)
    start = pos // w * w
    at = (layer, 0, start, 0, 0)
    chunk = jax.lax.dynamic_slice(
        buf, at, (1, buf.shape[1], w) + buf.shape[3:])
    hit = (jnp.arange(w) == pos - start)[None, None, :, None, None]
    chunk = jnp.where(hit, row.astype(buf.dtype)[None], chunk)
    return _stored_layout(jax.lax.dynamic_update_slice(buf, chunk, at))


def _stored_layout(x: jax.Array) -> jax.Array:
    """Hold the decode's stacked cache in row-major layout, as the TPU
    stores it when head_dim fills whole 128-lane rows. Without it XLA
    transposes the cache to suit the attention's dots: a relayout of the
    whole cache on the way into the layer loop and again on the way out,
    every token. A narrower head_dim is stored with positions minor, and
    pinning row-major would itself relayout it. A single KV head is
    placed major to the positions, as the runtime stores such a cache
    (row-major would tile the 1-wide heads axis with head_dim and
    relayout the whole cache in and out). Under an SPMD context the
    partitioner replicates a value that passes through a layout
    constraint (a whole-cache all-gather), so there XLA keeps the
    choice."""
    if sharding_active() or x.shape[-1] % 128:
        return x
    order = tuple(range(x.ndim))
    if x.shape[-2] == 1:
        order = order[:-3] + (x.ndim - 2, x.ndim - 3, x.ndim - 1)
    return with_layout_constraint(x, Layout(order))


def attn_decode(p: Params, spec: AttnSpec, x: jax.Array,
                cache: Dict[str, jax.Array], pos: jax.Array,
                layer: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: [B,1,d]; cache k/v: the whole stack's
    [L,B,S_max,Hkv,hd]; pos, layer scalars.

    Writes the token's k/v at (layer, :, pos) and returns the stack's
    updated cache: under donation XLA updates the buffer in place, and
    only this layer's slice is read.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    h = rmsnorm(x, p["norm"], spec.norm_eps)
    q, k, v = _proj(h, p["wq"]), _proj(h, p["wk"]), _proj(h, p["wv"])
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    tp = active_axis_size("tp")
    if tp > 1 and spec.n_kv_heads % tp != 0 and spec.head_dim % tp == 0:
        # KV cache is head_dim-sharded (kv_heads don't divide TP). Align
        # the (tiny) q/k/v the same way, or SPMD all-gathers the ENTIRE
        # cache at the score einsum — §Perf decode iteration E measured
        # 2.1GB/layer cache all-gathers vs 134MB score all-reduces.
        q = constrain(q, ("dp", None, None, "tp"))
        k = constrain(k, ("dp", None, None, "tp"))
        v = constrain(v, ("dp", None, None, "tp"))
    ck = _write_row(cache["k"], k, layer, pos)
    cv = _write_row(cache["v"], v, layer, pos)
    # slots beyond pos are masked by the causal relation on positions
    ck_l = jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False)
    cv_l = jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False)
    out = attention_ref(q, ck_l, cv_l, causal=True, window=spec.window,
                        q_positions=positions[0],
                        kv_positions=jnp.arange(ck_l.shape[1]))
    return x + _out_proj(out, p["wo"]), {"k": ck, "v": cv}


def cross_attn_decode(p: Params, spec: AttnSpec, x: jax.Array,
                      memory: Tuple[jax.Array, jax.Array]) -> jax.Array:
    mk, mv = memory
    h = rmsnorm(x, p["norm"], spec.norm_eps)
    q = _proj(h, p["wq"])
    out = attention_ref(q, mk, mv, causal=False)
    return x + _out_proj(out, p["wo"])


def cross_attn_memory(p: Params, spec: AttnSpec,
                      enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Precompute K/V of the encoder output for cross-attention."""
    return _proj(enc_out, p["wk"]), _proj(enc_out, p["wv"])


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLPSpec:
    d_model: int
    d_ff: int
    act: str                       # swiglu | squared_relu | gelu
    norm_eps: float


def mlp_init(b: ParamBuilder, spec: MLPSpec) -> None:
    d, f = spec.d_model, spec.d_ff
    b.add("norm", (d,), ("embed_nt",), init="ones")
    if spec.act == "swiglu":
        b.add("wg", (d, f), ("embed", "ff"))
        b.add("wu", (d, f), ("embed", "ff"))
    else:
        b.add("wu", (d, f), ("embed", "ff"))
    b.add("wd", (f, d), ("ff", "embed"), scale=1.0 / math.sqrt(f))


def mlp_core(p: Params, spec: MLPSpec, h: jax.Array) -> jax.Array:
    """The un-normed, un-residualed FFN body (shared with MoE experts)."""
    if spec.act == "swiglu":
        return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]
    if spec.act == "squared_relu":
        return jnp.square(jax.nn.relu(h @ p["wu"])) @ p["wd"]
    if spec.act == "gelu":
        return jax.nn.gelu(h @ p["wu"]) @ p["wd"]
    raise ValueError(spec.act)


def mlp_apply(p: Params, spec: MLPSpec, x: jax.Array) -> jax.Array:
    h = rmsnorm(x, p["norm"], spec.norm_eps)
    return x + mlp_core(p, spec, h)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(b: ParamBuilder, vocab: int, d_model: int, tie: bool) -> None:
    b.add("embedding", (vocab, d_model), ("vocab", "embed"), scale=0.02)
    if not tie:
        b.add("unembed", (d_model, vocab), ("embed", "vocab"),
              scale=1.0 / math.sqrt(d_model))


def embed_apply(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(dtype)


def unembed_apply(p: Params, x: jax.Array, tie: bool) -> jax.Array:
    # Logits stay in the compute dtype (bf16 for the large-vocab archs —
    # materializing f32 [B,S,V] would dominate HBM); the loss upcasts inside
    # its reductions, which XLA fuses.
    if tie:
        return jnp.einsum("bsd,vd->bsv", x, p["embedding"])
    return jnp.einsum("bsd,dv->bsv", x, p["unembed"])

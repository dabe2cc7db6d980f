"""Plain reference of a Jamba hybrid decoder, in float32.

Straightforward ``jax.numpy`` of the published Jamba block (arXiv
2403.19887; HF ``JambaForCausalLM``): a period of ``attn_layer_period``
layers, attention where ``j % period == attn_layer_offset`` and Mamba-1
elsewhere, each mixer followed by a SwiGLU MLP (``num_experts`` 1), all
pre-norm with residuals, a final RMSNorm and a tied or untied head.

- Mamba: RMSNorm, in_proj to (x, z), depthwise causal conv of width
  ``mamba_d_conv`` with bias, SiLU, x_proj to (dt, B, C), RMSNorms on dt, B
  and C, dt_proj with bias and softplus, then the selective recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = h_t C_t + D x_t``
  run one position at a time (a ``lax.scan`` over positions), gated by
  SiLU(z), out_proj. No projection biases (``mamba_proj_bias`` false).
- Attention: causal grouped-query attention with no positional encoding,
  computed over blocks of queries so that about 12k positions fit.

No kernels, no cache, no batching tricks; it imports nothing of the
program under test.

Weights come from the seed by the key schedule the program documents
for its initializer, stored in bf16 and read as float32:

- ``k_embed, k_stack, _ = split(seed_key, 3)``; from ``k_embed`` one key,
  split off, for the embedding (normal x 0.02, rows padded to a multiple
  of 256), and one more for the untied head; the final norm is ones.
- ``split(k_stack, n_groups)``: one key per period of 14 (or
  ``attn_layer_period``) layers. Within a period a key is split off for
  each block in layer order: layer j's mixer (``l{j}_mamba`` or
  ``l{j}_attn``), then its MLP (``l{j}_mlp``), so ``l0_mamba, l0_mlp,
  l1_mamba, l1_mlp, ..., l7_attn, l7_mlp, ..., l13_mamba, l13_mlp``.
- Within a block a key is split off for each normal leaf in the order
  listed below; zeros, ones, ``dt_bias`` and ``A_log`` take none. A
  normal leaf of shape (n, ...) is N(0, 1) x 1/sqrt(n) unless a scale is
  given.
  Mamba: norm (ones), in_proj (d, 2 di), conv_w (W, di) x 1/sqrt(W),
  conv_b (zeros), x_proj (di, R + 2N), dt_proj (R, di), dt_bias (the
  inverse softplus of dt log-spaced over [1e-3, 1e-1] across the di
  channels: Mamba's dt initialization, whose draws are log-uniform there),
  A_log (log 1..N along N, [di, N]), D (ones), out_proj (di, d), dt_norm,
  b_norm, c_norm (ones). Attention: norm, wq (d, h, hd), wk, wv (d, kv,
  hd), wo (h, hd, d) x 1/sqrt(h hd). MLP: norm, wg, wu (d, f), wd (f, d).

Every product is computed in float32 at ``Precision.HIGHEST``.
``lowp=True`` gives the control: every matmul takes its operands rounded
to float8 e4m3 with a per-tensor scale, the step below the
configuration's bf16.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per block of the reference's attention


def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    v = cfg["vocab_size"]
    period = cfg["attn_layer_period"]
    L = cfg["num_hidden_layers"]
    if L % period:
        raise ValueError(f"{L} layers are not whole periods of {period}")
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "L": L, "groups": L // period,
            "period": period, "offset": cfg["attn_layer_offset"],
            "di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
            "W": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
            "V": v, "Vp": -(-v // 256) * 256,
            "tied": bool(cfg.get("tie_word_embeddings", False)),
            "eps": cfg["rms_norm_eps"]}


def kinds(cfg: Dict[str, Any]) -> List[str]:
    """The mixer of each layer of a period: "attn" or "mamba"."""
    s = shapes(cfg)
    return ["attn" if j % s["period"] == s["offset"] else "mamba"
            for j in range(s["period"])]


# ------------------------------------------------------------------ init

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class _Keys:
    """One key per leaf, split off in build order."""

    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _dt_bias(di):
    """softplus^-1 of dt log-spaced over [1e-3, 1e-1] across channels."""
    dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), di,
                              dtype=jnp.float32))
    return dt + jnp.log(-jnp.expm1(-dt))


def _mamba_init(ks: _Keys, s, dtype):
    d, di, N, W, R = s["d"], s["di"], s["N"], s["W"], s["R"]
    A_log = jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                             (di, N))
    return {"norm": jnp.ones((d,), dtype),
            "in_proj": _normal(ks.next(), (d, 2 * di), 1 / math.sqrt(d),
                               dtype),
            "conv_w": _normal(ks.next(), (W, di), 1 / math.sqrt(W), dtype),
            "conv_b": jnp.zeros((di,), dtype),
            "x_proj": _normal(ks.next(), (di, R + 2 * N), 1 / math.sqrt(di),
                              dtype),
            "dt_proj": _normal(ks.next(), (R, di), 1 / math.sqrt(R), dtype),
            "dt_bias": _dt_bias(di).astype(dtype),
            "A_log": A_log.astype(dtype),
            "D": jnp.ones((di,), dtype),
            "out_proj": _normal(ks.next(), (di, d), 1 / math.sqrt(di), dtype),
            "dt_norm": jnp.ones((R,), dtype),
            "b_norm": jnp.ones((N,), dtype),
            "c_norm": jnp.ones((N,), dtype)}


def _attn_init(ks: _Keys, s, dtype):
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    return {"norm": jnp.ones((d,), dtype),
            "wq": _normal(ks.next(), (d, h, hd), 1 / math.sqrt(d), dtype),
            "wk": _normal(ks.next(), (d, kv, hd), 1 / math.sqrt(d), dtype),
            "wv": _normal(ks.next(), (d, kv, hd), 1 / math.sqrt(d), dtype),
            "wo": _normal(ks.next(), (h, hd, d), 1 / math.sqrt(h * hd),
                          dtype)}


def _mlp_init(ks: _Keys, s, dtype):
    d, f = s["d"], s["f"]
    return {"norm": jnp.ones((d,), dtype),
            "wg": _normal(ks.next(), (d, f), 1 / math.sqrt(d), dtype),
            "wu": _normal(ks.next(), (d, f), 1 / math.sqrt(d), dtype),
            "wd": _normal(ks.next(), (f, d), 1 / math.sqrt(f), dtype)}


def _group_init(cfg, key, dtype):
    s = shapes(cfg)
    ks = _Keys(key)
    out = {}
    for j, kind in enumerate(kinds(cfg)):
        init = _attn_init if kind == "attn" else _mamba_init
        out[f"l{j}_{kind}"] = init(_Keys(ks.next()), s, dtype)
        out[f"l{j}_mlp"] = _mlp_init(_Keys(ks.next()), s, dtype)
    return out


def make_init(cfg: Dict[str, Any]):
    """One jitted call: seed key -> bf16 weights on the device,
    {"embed": {...}, "stack": {"l0_mamba": ..., ...}} with a leading
    period axis on every stack leaf."""
    def init(key):
        s = shapes(cfg)
        k_embed, k_stack, _ = jax.random.split(key, 3)
        ke = _Keys(k_embed)
        dtype = jnp.bfloat16
        embed = {"embedding": _normal(ke.next(), (s["Vp"], s["d"]), 0.02,
                                      dtype)}
        if not s["tied"]:
            embed["unembed"] = _normal(ke.next(), (s["d"], s["Vp"]),
                                       1 / math.sqrt(s["d"]), dtype)
        embed["final_norm"] = jnp.ones((s["d"],), dtype)
        stack = jax.vmap(lambda k: _group_init(cfg, k, dtype))(
            jax.random.split(k_stack, s["groups"]))
        return {"embed": embed, "stack": stack}
    return jax.jit(init)


# -------------------------------------------------------- low precision

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale."""
    s = jnp.max(jnp.abs(x)) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(spec: str, a, b, lowp: bool):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# --------------------------------------------------------------- forward

def _f32(t):
    return t.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _mamba_fwd(s, lowp, x, p):
    """One Mamba layer (with its norm and residual) on x [S, d] (f32)."""
    eps, N, R, W = s["eps"], s["N"], s["R"], s["W"]
    S = x.shape[0]
    h = _rmsnorm(x, p["norm"], eps)
    xin, z = jnp.split(_mm("sd,de->se", h, _f32(p["in_proj"]), lowp), 2, -1)
    xp = jnp.concatenate([jnp.zeros((W - 1, xin.shape[1])), xin])
    xc = sum(xp[i:i + S] * _f32(p["conv_w"][i]) for i in range(W)) \
        + _f32(p["conv_b"])
    xc = jax.nn.silu(xc)
    xdb = _mm("se,er->sr", xc, _f32(p["x_proj"]), lowp)
    dt, Bm, Cm = jnp.split(xdb, [R, R + N], axis=-1)
    dt = _rmsnorm(dt, p["dt_norm"], eps)
    Bm = _rmsnorm(Bm, p["b_norm"], eps)
    Cm = _rmsnorm(Cm, p["c_norm"], eps)
    dt = jax.nn.softplus(_mm("sr,re->se", dt, _f32(p["dt_proj"]), lowp)
                         + _f32(p["dt_bias"]))
    A = -jnp.exp(_f32(p["A_log"]))                             # [di, N]

    def step(hs, inp):
        dt_t, x_t, B_t, C_t = inp
        hs = jnp.exp(dt_t[:, None] * A) * hs + (dt_t * x_t)[:, None] * B_t
        return hs, jnp.sum(hs * C_t, axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros(A.shape), (dt, xc, Bm, Cm))
    y = (y + _f32(p["D"]) * xc) * jax.nn.silu(z)
    return x + _mm("se,ed->sd", y, _f32(p["out_proj"]), lowp)


def _attn_fwd(s, lowp, x, p, block=Q_BLOCK):
    """One attention layer on x [S, d]: causal GQA, no positional
    encoding, over blocks of ``block`` queries."""
    S = x.shape[0]
    g = s["h"] // s["kv"]
    h = _rmsnorm(x, p["norm"], s["eps"])
    q = _mm("sd,dhk->shk", h, _f32(p["wq"]), lowp)
    k = _mm("sd,dhk->shk", h, _f32(p["wk"]), lowp)
    v = _mm("sd,dhk->shk", h, _f32(p["wv"]), lowp)
    nb = -(-S // block)
    qg = jnp.pad(q, ((0, nb * block - S), (0, 0), (0, 0))).reshape(
        nb, block, s["kv"], g, s["hd"])

    def one(args):
        i, qb = args
        sc = _mm("skgd,tkd->kgst", qb, k, lowp) / math.sqrt(s["hd"])
        causal = (i * block + jnp.arange(block))[:, None] \
            >= jnp.arange(S)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm("kgst,tkd->skgd", pr, v, lowp)

    o = jax.lax.map(one, (jnp.arange(nb), qg))
    o = o.reshape(nb * block, s["h"], s["hd"])[:S]
    return x + _mm("shk,hkd->sd", o, _f32(p["wo"]), lowp)


def _mlp_fwd(s, lowp, x, p):
    h = _rmsnorm(x, p["norm"], s["eps"])
    u = jax.nn.silu(_mm("sd,df->sf", h, _f32(p["wg"]), lowp)) \
        * _mm("sd,df->sf", h, _f32(p["wu"]), lowp)
    return x + _mm("sf,fd->sd", u, _f32(p["wd"]), lowp)


def hidden(cfg, params, tokens, lowp=False):
    """Final-normed hidden states [S, d] of one sequence: a scan over the
    stacked periods, the layers of a period in order."""
    s = shapes(cfg)
    order = kinds(cfg)
    x = _f32(params["embed"]["embedding"][tokens])

    def body(x, p):
        for j, kind in enumerate(order):
            fwd = _attn_fwd if kind == "attn" else _mamba_fwd
            x = fwd(s, lowp, x, p[f"l{j}_{kind}"])
            x = _mlp_fwd(s, lowp, x, p[f"l{j}_mlp"])
        return x, None

    x, _ = jax.lax.scan(body, x, params["stack"])
    return _rmsnorm(x, params["embed"]["final_norm"], s["eps"])


def head(cfg, params, x, lowp=False):
    """Logits [S, Vp] of hidden states x [S, d]."""
    e = params["embed"]
    if cfg.get("tie_word_embeddings", False):
        return _mm("sd,vd->sv", x, _f32(e["embedding"]), lowp)
    return _mm("sd,dv->sv", x, _f32(e["unembed"]), lowp)


# --------------------------------------------------------------- serving

def token_gaps(cfg, params, prompt: np.ndarray, served: np.ndarray,
               targets: np.ndarray = None, lowp: bool = False,
               block: int = 512) -> Tuple[np.ndarray, np.ndarray]:
    """For one request whose context is the prompt and the served tokens:
    at each position that produced a served token, the gap
    max_v logit[v] - logit[target] (targets default to the served tokens)
    and the token this precision puts first there. ``served[0]`` comes
    from the prompt's last position."""
    targets = served if targets is None else targets
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    P = len(prompt)
    x = _hidden_jit(cfg, lowp)(params, jnp.asarray(seq))
    gaps, firsts = [], []
    for lo in range(P - 1, len(seq), block):
        hi = min(lo + block, len(seq))
        tgt = jnp.asarray(targets[lo - P + 1:hi - P + 1].astype(np.int32))
        g, f = _gap_jit(cfg, lowp)(params, x[lo:hi], tgt)
        gaps.append(np.asarray(g))
        firsts.append(np.asarray(f))
    return np.concatenate(gaps), np.concatenate(firsts)


_JITS: Dict[Tuple[Any, str, bool], Any] = {}


def _key(cfg):
    return tuple(sorted((k, repr(v)) for k, v in cfg.items()))


def _hidden_jit(cfg, lowp):
    key = (_key(cfg), "hidden", lowp)
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, t: hidden(cfg, p, t, lowp))
    return _JITS[key]


def _gap_jit(cfg, lowp):
    key = (_key(cfg), "gap", lowp)
    if key not in _JITS:
        def f(p, x, tgt):
            lg = head(cfg, p, x, lowp)
            best = jnp.max(lg, -1)
            return (best - jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0],
                    jnp.argmax(lg, -1))
        _JITS[key] = jax.jit(f)
    return _JITS[key]

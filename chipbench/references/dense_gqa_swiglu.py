"""Plain reference of a dense GQA SwiGLU decoder, in float32.

Straightforward ``jax.numpy``: RMSNorm, rotate-half RoPE, causal
grouped-query attention, SwiGLU MLP, untied or tied head, masked token
cross-entropy, AdamW with global-norm clipping. No kernels, no cache, no
batching tricks. It imports nothing of the program under test.

Weights come from the seed by the same key schedule the program
documents for its initializer (one split of the seed into embedding and
stack keys, one key per layer, one per leaf in build order), stored in the
configuration's parameter dtype (bf16); every product is computed from them
in float32 at ``Precision.HIGHEST``.

``lowp=True`` gives the control: every matmul takes its operands and its
backward cotangent rounded to float8 e4m3 with a per-tensor scale, the
step below the configuration's bf16.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    v = cfg["vocab_size"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": v, "Vp": -(-v // 256) * 256,
            "tied": bool(cfg.get("tie_word_embeddings", False))}


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


def _layer(key, s, dtype):
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    ks = _Keys(key)
    ka = _Keys(ks.next())
    attn = {"norm": jnp.ones((d,), dtype),
            "wq": _normal(ka.next(), (d, h, hd), 1 / math.sqrt(d), dtype),
            "wk": _normal(ka.next(), (d, kv, hd), 1 / math.sqrt(d), dtype),
            "wv": _normal(ka.next(), (d, kv, hd), 1 / math.sqrt(d), dtype),
            "wo": _normal(ka.next(), (h, hd, d), 1 / math.sqrt(h * hd),
                          dtype)}
    km = _Keys(ks.next())
    mlp = {"norm": jnp.ones((d,), dtype),
           "wg": _normal(km.next(), (d, f), 1 / math.sqrt(d), dtype),
           "wu": _normal(km.next(), (d, f), 1 / math.sqrt(d), dtype),
           "wd": _normal(km.next(), (f, d), 1 / math.sqrt(f), dtype)}
    return {"l0_attn": attn, "l0_mlp": mlp}


def make_init(cfg: Dict[str, Any]):
    """One jitted call: seed -> bf16 weights on the device."""
    return jax.jit(lambda seed_key: _init_from_key(cfg, seed_key))


def _init_from_key(cfg, key):
    """{"embed": {...}, "stack": {"l0_attn": ..., "l0_mlp": ...}} with a
    leading layer axis on every stack leaf."""
    s = shapes(cfg)
    k_embed, k_stack, _ = jax.random.split(key, 3)
    ke = _Keys(k_embed)
    dtype = jnp.bfloat16
    embed = {"embedding": _normal(ke.next(), (s["Vp"], s["d"]), 0.02, dtype)}
    if not s["tied"]:
        embed["unembed"] = _normal(ke.next(), (s["d"], s["Vp"]),
                                   1 / math.sqrt(s["d"]), dtype)
    embed["final_norm"] = jnp.ones((s["d"],), dtype)
    stack = jax.vmap(lambda k: _layer(k, s, dtype))(
        jax.random.split(k_stack, s["L"]))
    return {"embed": embed, "stack": stack}


# -------------------------------------------------------- low precision

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale."""
    s = jnp.max(jnp.abs(x)) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


@jax.custom_vjp
def _grad_fp8(x):
    return x


_grad_fp8.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def _mm(spec: str, a, b, lowp: bool):
    if not lowp:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    ste = lambda t: t + jax.lax.stop_gradient(_fp8(t) - t)   # noqa: E731
    return _grad_fp8(jnp.einsum(spec, ste(a), ste(b), precision=HIGHEST))


# --------------------------------------------------------------- forward

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs       # [S,1,hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_fwd(cfg, lowp, x, p):
    """One decoder layer on one sequence x [S, d] (f32)."""
    s = shapes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = lambda t: t.astype(jnp.float32)                     # noqa: E731
    a, m = p["l0_attn"], p["l0_mlp"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rmsnorm(x, f32(a["norm"]), eps)
    q = _rope(_mm("sd,dhk->shk", h, f32(a["wq"]), lowp), pos, theta)
    k = _rope(_mm("sd,dhk->shk", h, f32(a["wk"]), lowp), pos, theta)
    v = _mm("sd,dhk->shk", h, f32(a["wv"]), lowp)
    g = s["h"] // s["kv"]
    qg = q.reshape(S, s["kv"], g, s["hd"])
    sc = _mm("skgd,tkd->kgst", qg, k, lowp) / math.sqrt(s["hd"])
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _mm("kgst,tkd->skgd", pr, v, lowp).reshape(S, s["h"], s["hd"])
    x = x + _mm("shk,hkd->sd", o, f32(a["wo"]), lowp)
    h = _rmsnorm(x, f32(m["norm"]), eps)
    u = jax.nn.silu(_mm("sd,df->sf", h, f32(m["wg"]), lowp)) \
        * _mm("sd,df->sf", h, f32(m["wu"]), lowp)
    return x + _mm("sf,fd->sd", u, f32(m["wd"]), lowp)


def hidden(cfg, params, tokens, lowp=False):
    """Final-normed hidden states [S, d] of one sequence, layer by layer
    (a scan over the stacked layers; each layer rematerialized)."""
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    body = jax.checkpoint(lambda x, p: (_layer_fwd(cfg, lowp, x, p), None))
    x, _ = jax.lax.scan(body, x, params["stack"])
    return _rmsnorm(x, params["embed"]["final_norm"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


def head(cfg, params, x, lowp=False):
    """Logits [S, Vp] of hidden states x [S, d]."""
    e = params["embed"]
    if cfg.get("tie_word_embeddings", False):
        return _mm("sd,vd->sv", x, e["embedding"].astype(jnp.float32), lowp)
    return _mm("sd,dv->sv", x, e["unembed"].astype(jnp.float32), lowp)


# -------------------------------------------------------------- training

def _row_nll_sum(cfg, lowp, params, tokens, targets):
    x = hidden(cfg, params, tokens, lowp)
    logits = head(cfg, params, x, lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, jnp.clip(targets, 0)[:, None], -1)[:, 0]
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum((lse - tl) * mask)


def loss_and_grad(cfg, params, tokens, targets, lowp=False):
    """Mean token loss of a batch and its f32 gradient w.r.t. the (bf16)
    weights read as f32, accumulated one row at a time."""
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    n = jnp.maximum(jnp.sum(targets >= 0), 1).astype(jnp.float32)
    vg = jax.value_and_grad(functools.partial(_row_nll_sum, cfg, lowp))

    def body(acc, row):
        l, g = vg(p32, row[0], row[1])
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p32))
    (total, grads), _ = jax.lax.scan(body, zero, (tokens, targets))
    return total / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(opt: Dict[str, Any], count) -> jax.Array:
    if opt.get("schedule", "constant") != "constant":
        raise ValueError("the reference follows a constant schedule only")
    warm = jnp.minimum(1.0, (count.astype(jnp.float32) + 1.0)
                       / max(opt["warmup_steps"], 1))
    return opt["lr"] * warm


def adamw(opt, params, grads, m, v, count):
    """One AdamW update in f32; weights rounded back to their dtype. Decay
    applies to every leaf of two or more axes, as the trainer defines it
    on its stacked leaves."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm > opt["grad_clip"],
                      opt["grad_clip"] / (gnorm + 1e-9), 1.0)
    lr = lr_at(opt, count)
    c = (count + 1).astype(jnp.float32)
    bc1, bc2 = 1.0 - opt["b1"] ** c, 1.0 - opt["b2"] ** c

    def upd(p, g, m, v):
        g = g * scale
        m = opt["b1"] * m + (1 - opt["b1"]) * g
        v = opt["b2"] * v + (1 - opt["b2"]) * g * g
        step = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        pf = p.astype(jnp.float32)
        decay = opt["weight_decay"] * pf if p.ndim >= 2 else 0.0
        return (pf - lr * (step + decay)).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,          # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), count + 1


def train_steps(cfg, opt, seed, batches, lowp=False):
    """Follow the first ``len(batches)`` steps from the seed. Returns the
    losses, each leaf's first gradient as the optimizer takes it (the
    first moment after one step over 1 - b1) as host arrays of norms, the
    initial and final weights on the host."""
    params = make_init(cfg)(jax.random.PRNGKey(seed))
    p0 = jax.device_get(params)
    m = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    v = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    count = jnp.zeros((), jnp.int32)

    @jax.jit
    def step(params, m, v, count, tokens, targets):
        loss, grads = loss_and_grad(cfg, params, tokens, targets, lowp)
        params, m, v, count = adamw(opt, params, grads, m, v, count)
        return params, m, v, count, loss

    losses, g1 = [], None
    for i, b in enumerate(batches):
        params, m, v, count, loss = step(params, m, v, count,
                                         jnp.asarray(b["tokens"]),
                                         jnp.asarray(b["targets"]))
        losses.append(float(loss))
        if i == 0:
            g1 = leaf_norms(jax.tree.map(lambda t: t / (1 - opt["b1"]), m))
    return losses, g1, p0, jax.device_get(params)


def leaf_norms(tree) -> Dict[str, float]:
    """path -> f32 norm of every leaf, computed on the device."""
    flat = flatten(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])(list(flat.values()))
    return dict(zip(flat, (float(n) for n in jax.device_get(norms))))


def flatten(tree) -> Dict[str, Any]:
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = x
    return out


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


_JITS: Dict[Tuple[str, str, bool], Any] = {}


def _hidden_jit(cfg, lowp):
    key = (cfg["name"], "hidden", lowp)
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, t: hidden(cfg, p, t, lowp))
    return _JITS[key]


def _gap_jit(cfg, lowp):
    key = (cfg["name"], "gap", lowp)
    if key not in _JITS:
        def f(p, x, tgt):
            lg = head(cfg, p, x, lowp)
            best = jnp.max(lg, -1)
            return (best - jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0],
                    jnp.argmax(lg, -1))
        _JITS[key] = jax.jit(f)
    return _JITS[key]

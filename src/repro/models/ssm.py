"""Mamba-1 selective SSM block (jamba's recurrent layer).

TPU adaptation: the selective scan runs position by position, a ``lax.scan``
unrolled ``UNROLL`` times whose step builds that position's decay and input
term ([B, N, d_inner], from its dt, x, B and C) and updates the state, so
live buffers stay at the state's size whatever the sequence length. On one
TPU v5e this is ten times faster than a chunked parallel scan: one
Jamba2-3B layer's 8,192-token prefill took 14.6 ms so, and 151 ms as 32
chunks of an ``associative_scan`` over 256 positions. Decode is the same
step on the cached state. The state keeps d_inner minor: with the
16-wide d_state minor the TPU would pad every state buffer to 128 lanes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import ParamBuilder, rmsnorm

Params = Any
UNROLL = 8


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_model: int
    cfg: SSMConfig
    norm_eps: float

    @property
    def d_inner(self) -> int:
        return self.cfg.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.cfg.rank(self.d_model)


def mamba_init(b: ParamBuilder, spec: MambaSpec) -> None:
    d, di, R, N = spec.d_model, spec.d_inner, spec.dt_rank, spec.cfg.d_state
    W = spec.cfg.d_conv
    b.add("norm", (d,), ("embed_nt",), init="ones")
    b.add("in_proj", (d, 2 * di), ("embed", "ssm_inner"))
    b.add("conv_w", (W, di), (None, "ssm_inner_nt"), scale=1.0 / math.sqrt(W))
    b.add("conv_b", (di,), ("ssm_inner_nt",), init="zeros")
    b.add("x_proj", (di, R + 2 * N), ("ssm_inner", None))
    b.add("dt_proj", (R, di), (None, "ssm_inner"), scale=1.0 / math.sqrt(R))
    b.add("dt_bias", (di,), ("ssm_inner_nt",), init="mamba_dt")
    b.add("A_log", (di, N), ("ssm_inner_nt", None), init="s4d_real")
    b.add("D", (di,), ("ssm_inner_nt",), init="ones")
    b.add("out_proj", (di, d), ("ssm_inner", "embed"),
          scale=1.0 / math.sqrt(di))
    if spec.cfg.inner_norms:
        b.add("dt_norm", (R,), (None,), init="ones")
        b.add("b_norm", (N,), (None,), init="ones")
        b.add("c_norm", (N,), (None,), init="ones")


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array = None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: [B,S,di]; w: [W,di]. Returns (y, new_state).

    state: [B, W-1, di] — trailing inputs from the previous segment.
    """
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W)) + b
    return y, xp[:, -(W - 1):]


def _ssm_params(p: Params, spec: MambaSpec, x: jax.Array):
    """x: [B,S,di] (post-conv, post-silu) -> per-step (dt [B,S,di] f32,
    B [B,S,N] f32, C [B,S,N] f32), with Jamba's norms on dt, B and C."""
    N, R = spec.cfg.d_state, spec.dt_rank
    xdb = x @ p["x_proj"]                                     # [B,S,R+2N]
    dt_r, Bm, Cm = jnp.split(xdb, [R, R + N], axis=-1)
    if spec.cfg.inner_norms:
        dt_r = rmsnorm(dt_r, p["dt_norm"], spec.norm_eps)
        Bm = rmsnorm(Bm, p["b_norm"], spec.norm_eps)
        Cm = rmsnorm(Cm, p["c_norm"], spec.norm_eps)
    f32 = jnp.float32
    dt = jax.nn.softplus((dt_r @ p["dt_proj"]).astype(f32)
                         + p["dt_bias"].astype(f32))          # [B,S,di]
    return dt, Bm.astype(f32), Cm.astype(f32)


def _neg_a(p: Params) -> jax.Array:
    """-exp(A_log), transposed to [N, di]."""
    return -jnp.exp(p["A_log"].astype(jnp.float32)).T


def _scan_terms(A: jax.Array, dt: jax.Array, x: jax.Array, Bm: jax.Array):
    """Decays exp(dt A) and inputs dt x B of the recurrence
    h_t = decay_t * h_{t-1} + input_t, each [..., N, di] (f32)."""
    decay = jnp.exp(dt[..., None, :] * A)
    inp = (dt * x.astype(jnp.float32))[..., None, :] * Bm[..., :, None]
    return decay, inp


def _read_out(h: jax.Array, C: jax.Array) -> jax.Array:
    """y = sum_n h[..., n, :] C[..., n] in f32 on the vector unit (a dot
    would round the state to bf16 on the TPU's matrix unit)."""
    return jnp.sum(h * C[..., None], axis=-2)


def _step(A: jax.Array, h: jax.Array, dt: jax.Array, x: jax.Array,
          Bm: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One position of the recurrence h = exp(dt A) h + dt x B, read out
    by C. h: [B,N,di]; dt, x: [B,di]; Bm, C: [B,N]. Returns (h, y [B,di])."""
    decay, u = _scan_terms(A, dt, x, Bm)
    h = decay * h + u
    return h, _read_out(h, C)


def _mamba_forward(p: Params, spec: MambaSpec, x: jax.Array,
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Shared train/prefill forward. Returns (out, cache)."""
    B, S, d = x.shape
    di, N = spec.d_inner, spec.cfg.d_state
    h0 = rmsnorm(x, p["norm"], spec.norm_eps)
    xin, z = jnp.split(h0 @ p["in_proj"], 2, axis=-1)
    xc, conv_state = _causal_conv(xin, p["conv_w"], p["conv_b"])
    xc = jax.nn.silu(xc)
    dt, Bm, Cm = _ssm_params(p, spec, xc)
    A = _neg_a(p)                                             # [N,di]

    by_position = lambda t: jnp.moveaxis(t, 1, 0)             # [S,B,...]
    h_last, ys = jax.lax.scan(
        lambda h, inp: _step(A, h, *inp),
        jnp.zeros((B, N, di), jnp.float32),
        (by_position(dt), by_position(xc), by_position(Bm), by_position(Cm)),
        unroll=UNROLL)
    y = jnp.moveaxis(ys, 0, 1)                                # [B,S,di]
    y = (y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)).astype(x.dtype)
    out = (y * jax.nn.silu(z)) @ p["out_proj"]
    return x + out, {"h": h_last, "conv": conv_state}


def mamba_apply(p: Params, spec: MambaSpec, x: jax.Array) -> jax.Array:
    """Training forward. x: [B,S,d] -> [B,S,d] (with residual)."""
    return _mamba_forward(p, spec, x)[0]


def mamba_prefill(p: Params, spec: MambaSpec, x: jax.Array,
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    return _mamba_forward(p, spec, x)


def mamba_cache_init(spec: MambaSpec, batch: int, dtype) -> Dict[str, Any]:
    di, N, W = spec.d_inner, spec.cfg.d_state, spec.cfg.d_conv
    return {
        "h": jnp.zeros((batch, N, di), jnp.float32),
        "conv": jnp.zeros((batch, W - 1, di), dtype),
    }


def mamba_decode(p: Params, spec: MambaSpec, x: jax.Array,
                 cache: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode. x: [B,1,d]."""
    h0 = rmsnorm(x, p["norm"], spec.norm_eps)
    xin, z = jnp.split(h0 @ p["in_proj"], 2, axis=-1)
    xc, conv_state = _causal_conv(xin, p["conv_w"], p["conv_b"], cache["conv"])
    xc = jax.nn.silu(xc)
    dt, Bm, Cm = _ssm_params(p, spec, xc)                     # S=1
    h_new, y = _step(_neg_a(p), cache["h"], dt[:, 0], xc[:, 0], Bm[:, 0],
                     Cm[:, 0])
    y = y[:, None]
    y = (y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)).astype(x.dtype)
    out = (y * jax.nn.silu(z)) @ p["out_proj"]
    return x + out, {"h": h_new, "conv": conv_state}

"""Model factory: ArchConfig -> Model (init / loss / prefill / decode).

The Model is the unit the rest of the system operates on:
  * the trainer builds ``train_step`` from ``model.loss``;
  * the serve engine builds ``prefill`` / ``decode_step``;
  * the CACS checkpoint service snapshots ``{params, opt_state, data_state}``
    pytrees produced here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.sharding.specs import constrain

Params = Any


def _pad_vocab(v: int) -> int:
    return ((v + 255) // 256) * 256


@jax.custom_vjp
def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Masked token CE. targets: int32, -1 = ignore.

    Dtype-preserving with a custom VJP: every [B,S,V]-shaped tensor (exp,
    softmax, one-hot, d_logits) stays in the compute dtype; only scalar/
    [B,S] reductions run in f32. §Perf iterations B1/B2 measured plain
    autodiff materializing 4-6 f32 [B,S,V] tensors per step (the f32
    cotangent of the f32-accumulated V-reduction broadcasts before the
    downcast) — this VJP removes all of them.
    """
    return _ce_fwd(logits, targets)[0]


def _ce_fwd(logits, targets):
    m = jnp.max(logits, axis=-1, keepdims=True)          # compute dtype
    ex = jnp.exp(logits - m)                             # compute dtype
    sumexp = jnp.sum(ex, axis=-1, dtype=jnp.float32)     # f32 [B,S]
    lse = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
    tgt = jnp.clip(targets, 0, logits.shape[-1] - 1)
    tl = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = lse - tl.astype(jnp.float32)
    mask = (targets >= 0).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / n
    return loss, (ex, sumexp, tgt, mask, n)


def _ce_bwd(res, g):
    ex, sumexp, tgt, mask, n = res
    dt = ex.dtype
    inv = (1.0 / sumexp).astype(dt)[..., None]           # [B,S,1]
    scale = (g * mask / n).astype(dt)[..., None]         # [B,S,1]
    onehot = jax.nn.one_hot(tgt, ex.shape[-1], dtype=dt)
    d_logits = (ex * inv - onehot) * scale               # compute dtype
    return d_logits, None


cross_entropy.defvjp(_ce_fwd, _ce_bwd)


@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    def __post_init__(self):
        self.blocks, self.n_groups = T.build_group(self.cfg)
        if self.cfg.encoder is not None:
            self.enc_blocks, self.enc_groups = T.build_encoder_group(self.cfg)
        else:
            self.enc_blocks, self.enc_groups = None, 0
        self.dtype = jnp.dtype(self.cfg.dtype)
        self.vocab_padded = _pad_vocab(self.cfg.vocab_size)

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        k_embed, k_stack, k_enc = jax.random.split(key, 3)
        eb = L.ParamBuilder(k_embed, self.dtype)
        L.embed_init(eb, self.vocab_padded, cfg.d_model, cfg.tie_embeddings)
        eb.add("final_norm", (cfg.d_model,), ("embed_nt",), init="ones")
        stack = T.init_stack(k_stack, self.blocks, self.n_groups, self.dtype)
        params = {"embed": eb.params, "stack": stack}
        if self.enc_blocks is not None:
            enc_stack = T.init_stack(k_enc, self.enc_blocks,
                                     self.enc_groups, self.dtype)
            enb = L.ParamBuilder(k_enc, self.dtype)
            enb.add("final_norm", (cfg.d_model,), ("embed_nt",), init="ones")
            params["encoder"] = {"stack": enc_stack, **enb.params}
        return params

    def param_dims(self) -> Any:
        """Logical-dims pytree matching ``init`` output (no allocation)."""
        cfg = self.cfg
        dims_embed = {"embedding": ("vocab", "embed"),
                      "final_norm": ("embed_nt",)}
        if not cfg.tie_embeddings:
            dims_embed["unembed"] = ("embed", "vocab")
        dims = {"embed": dims_embed, "stack": T.stack_dims(self.blocks)}
        if self.enc_blocks is not None:
            dims["encoder"] = {"stack": T.stack_dims(self.enc_blocks),
                               "final_norm": ("embed_nt",)}
        return dims

    def abstract_params(self) -> Any:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------
    # Shared embedding / frontend handling
    # ------------------------------------------------------------------
    def _embed_tokens(self, params: Params, tokens: jax.Array) -> jax.Array:
        return L.embed_apply(params["embed"], tokens, self.dtype)

    def _encoder_forward(self, params: Params, frames: jax.Array,
                         remat: bool) -> jax.Array:
        enc = params["encoder"]
        positions = jnp.arange(frames.shape[1])
        x = constrain(frames.astype(self.dtype), ("dp", "sp", None))
        x, _ = T.stack_forward(enc["stack"], self.enc_blocks, x, positions,
                               remat=remat)
        return L.rmsnorm(x, enc["final_norm"], self.cfg.norm_eps)

    def _inputs(self, params: Params, batch: Dict[str, jax.Array],
                remat: bool = True,
                ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
        """-> (x [B,S,d], positions [S], enc_out or None)."""
        cfg = self.cfg
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._encoder_forward(params, batch["frames"], remat)
            x = self._embed_tokens(params, batch["tokens"])
        elif cfg.frontend is not None:           # vlm: prepend patch embeds
            tx = self._embed_tokens(params, batch["tokens"])
            fe = batch["patch_embeds"].astype(self.dtype)
            x = jnp.concatenate([fe, tx], axis=1)
        else:
            x = self._embed_tokens(params, batch["tokens"])
        positions = jnp.arange(x.shape[1])
        return x, positions, enc_out

    # ------------------------------------------------------------------
    # Training loss
    # ------------------------------------------------------------------
    def loss(self, params: Params, batch: Dict[str, jax.Array], *,
             remat=True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x, positions, enc_out = self._inputs(params, batch, remat)
        x = constrain(x, ("dp", "sp", None))
        x, aux = T.stack_forward(params["stack"], self.blocks, x, positions,
                                 enc_out=enc_out, remat=remat)
        x = L.rmsnorm(x, params["embed"]["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], x, cfg.tie_embeddings)
        logits = constrain(logits, ("dp", None, "tp"))
        ce = cross_entropy(logits, batch["targets"])
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "moe_aux": aux}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def prefill(self, params: Params, batch: Dict[str, jax.Array], *,
                cache_len: Optional[int] = None,
                ) -> Tuple[jax.Array, Params]:
        """Run the prompt; returns (last-position logits [B,V], cache)."""
        cfg = self.cfg
        x, positions, enc_out = self._inputs(params, batch, remat=False)
        x = constrain(x, ("dp", "sp", None))
        x, cache = T.stack_prefill(params["stack"], self.blocks, x, positions,
                                   enc_out=enc_out, cache_len=cache_len)
        x = L.rmsnorm(x[:, -1:], params["embed"]["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], x, cfg.tie_embeddings)
        return logits[:, 0], cache

    def decode_step(self, params: Params, cache: Params, token: jax.Array,
                    pos: jax.Array) -> Tuple[jax.Array, Params]:
        """token: [B,1] int32; pos: scalar int32. -> (logits [B,V], cache)."""
        cfg = self.cfg
        x = self._embed_tokens(params, token)
        x, cache = T.stack_decode(params["stack"], self.blocks, x, cache, pos)
        x = L.rmsnorm(x, params["embed"]["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], x, cfg.tie_embeddings)
        return logits[:, 0], cache

    # ------------------------------------------------------------------
    # Caches
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int) -> Params:
        enc_len = self.cfg.frontend_len if self.cfg.family == "encdec" else 0
        return T.init_cache(self.cfg, self.blocks, self.n_groups, batch,
                            cache_len, self.dtype, enc_len=enc_len)

    def abstract_cache(self, batch: int, cache_len: int) -> Params:
        return jax.eval_shape(lambda: self.init_cache(batch, cache_len))

    def cache_dims(self) -> Any:
        return T.cache_dims(self.blocks)

    # ------------------------------------------------------------------
    # Batch construction (synthetic shapes; the data pipeline mirrors this)
    # ------------------------------------------------------------------
    def batch_struct(self, global_batch: int, seq_len: int) -> Dict[str, Any]:
        """ShapeDtypeStructs for one training batch."""
        cfg = self.cfg
        B, S = global_batch, seq_len
        sds = jax.ShapeDtypeStruct
        if cfg.family == "encdec":
            return {
                "frames": sds((B, cfg.frontend_len, cfg.d_model), self.dtype),
                "tokens": sds((B, S), jnp.int32),
                "targets": sds((B, S), jnp.int32),
            }
        if cfg.frontend is not None:
            F = cfg.frontend_len
            return {
                "patch_embeds": sds((B, F, cfg.d_model), self.dtype),
                "tokens": sds((B, S - F), jnp.int32),
                "targets": sds((B, S), jnp.int32),
            }
        return {"tokens": sds((B, S), jnp.int32),
                "targets": sds((B, S), jnp.int32)}

    def batch_dims(self) -> Dict[str, Tuple]:
        cfg = self.cfg
        out = {"tokens": ("batch", None), "targets": ("batch", None)}
        if cfg.family == "encdec":
            out["frames"] = ("batch", None, None)
        elif cfg.frontend is not None:
            out["patch_embeds"] = ("batch", None, None)
        return out


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)

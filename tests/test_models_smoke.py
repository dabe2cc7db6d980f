"""Per-arch smoke tests (deliverable f): reduced same-family config, one
forward/train step on CPU, output shapes + no NaNs; decode path parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.models import build_model
from repro.models import layers as L
from repro.models import transformer as T
from tests.conftest import make_batch

ARCHS = sorted(ASSIGNED_ARCHS) + ["repro-100m"]


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = make_batch(cfg, model, B, S)
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g))), f"{arch}: NaN grad at {path}"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_smoke(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    cache = model.init_cache(B, S)
    logits, cache2 = model.decode_step(
        params, cache, jnp.ones((B, 1), jnp.int32), jnp.int32(3))
    assert logits.shape == (B, model.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN decode logits"
    # cache structure unchanged
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def _check_prefill_then_one_decode(cfg):
    """prefill(t0..tk) then decode(t_{k+1}) equals the prefill of
    t0..t_{k+1}: its logits for the token after the last."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 16
    rng = np.random.Generator(np.random.PCG64(1))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    logits_full, _ = model.prefill(params, {"tokens": tokens},
                                   cache_len=S + 1)
    _, cache = model.prefill(params, {"tokens": tokens[:, :-1]},
                             cache_len=S + 1)
    logits_dec, _ = model.decode_step(params, cache, tokens[:, -1:],
                                      jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    """Teacher-forcing parity: a prefix's prefill, then each of 4 decode
    steps, must equal the full forward's next-token logits (exactness
    varies with recurrent-state dtype; tolerance covers bf16 archs). Each
    step writes only cache row ``pos`` of every attention layer: every
    other position stays bit for bit as it was. MoE archs are first
    checked at their own capacity: the whole sequence's prefill against a
    shorter prefill and one decode step."""
    cfg = dataclasses.replace(reduced(get_config(arch)), dtype="float32")
    if cfg.moe is not None:
        _check_prefill_then_one_decode(cfg)
        # room in every expert for every token: a forward that drops a
        # token over capacity has no decode to match
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, n_dec = 1, 16, 4
    batch = make_batch(cfg, model, B, S, seed=1)
    batch.pop("targets")
    tokens = batch.pop("tokens")          # the rest: frames / patch embeds
    first = S - n_dec                     # position of the first decode
    n_tok = tokens.shape[1]               # S less any frontend positions
    attn = [b.name for b in model.blocks if b.kind == "attn"]

    # the full forward's logits at every position, in one pass
    x, positions, enc_out = model._inputs(
        params, {**batch, "tokens": tokens}, remat=False)
    x, _ = T.stack_forward(params["stack"], model.blocks, x, positions,
                           enc_out=enc_out, remat=False)
    x = L.rmsnorm(x, params["embed"]["final_norm"], cfg.norm_eps)
    logits_all = L.unembed_apply(params["embed"], x, cfg.tie_embeddings)

    logits_pre, cache = model.prefill(
        params, {**batch, "tokens": tokens[:, :n_tok - n_dec]}, cache_len=S)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_all[:, first - 1]),
                               rtol=2e-4, atol=2e-4)
    decode = jax.jit(model.decode_step)
    for pos in range(first, S):
        t = pos - (S - n_tok)
        logits_full = logits_all[:, pos]
        logits_dec, new = decode(params, cache, tokens[:, t:t + 1],
                                 jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(logits_dec),
                                   np.asarray(logits_full),
                                   rtol=2e-4, atol=2e-4)
        others = np.arange(S) != pos
        for name in attn:
            for kv in ("k", "v"):
                was, now = (np.asarray(c[name][kv]) for c in (cache, new))
                np.testing.assert_array_equal(now[:, :, others],
                                              was[:, :, others])
                assert not np.array_equal(now[:, :, pos], was[:, :, pos])
        cache = new


@pytest.mark.parametrize("arch", ["gemma3-12b"])
def test_sliding_window_masks(arch):
    """A token beyond the window must not influence local-layer outputs."""
    from repro.models.layers import attention_ref
    q = jnp.ones((1, 8, 2, 4))
    k = jnp.ones((1, 8, 2, 4))
    v = jnp.arange(8, dtype=jnp.float32)[None, :, None, None] * jnp.ones(
        (1, 8, 2, 4))
    out_w = attention_ref(q, k, v, causal=True, window=2)
    # at position 7 with window 2, only keys 6,7 are visible -> mean 6.5
    np.testing.assert_allclose(np.asarray(out_w[0, 7, 0, 0]), 6.5, atol=1e-5)

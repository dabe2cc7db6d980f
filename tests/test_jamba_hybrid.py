"""The Jamba hybrid (Mamba-1 + attention, one cache of two kinds) against
the benchmark's plain reference, on a reduced Jamba2-3B: one 14-layer
period (attention at layer 7), small widths, d_state 16, seeded weights.

The reference (``chipbench/references/jamba_hybrid.py``) is loaded by
path: it imports nothing of the program, and draws its own weights from
the seed by the key schedule the program documents.
"""
import dataclasses
import functools
import importlib.util
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import InMemoryStore
from repro.clusters import SnoozeBackend
from repro.configs import get_config, reduced
from repro.core import ASR, CACSService, CheckpointPolicy, CoordState
from repro.models import build_model
from repro.models import layers as L
from repro.models import ssm as S
from repro.serve.engine import Engine, ServeApp

ROOT = Path(__file__).resolve().parents[1]
CFG = reduced(get_config("jamba2-3b"))
# Program and reference both compute in f32 here; they differ only in the
# order of their sums (blocked attention, the matmuls' tiling, XLA's
# fusion of the scan's step): 4.3e-6 of the logits' scale.
# 1e-4 leaves that twenty times over, and each planted fault below moves
# the logits by a fifth of their scale or more (0.22 to 1.44).
TOL = 1e-4


def _reference():
    path = ROOT / "chipbench" / "references" / "jamba_hybrid.py"
    spec = importlib.util.spec_from_file_location("jamba_hybrid_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def ref_cfg(a):
    """The reference's configuration (config.json keys) of an ArchConfig."""
    return {"name": a.name, "hidden_size": a.d_model,
            "num_attention_heads": a.n_heads,
            "num_key_value_heads": a.n_kv_heads, "head_dim": a.head_dim,
            "intermediate_size": a.d_ff, "num_hidden_layers": a.n_layers,
            "attn_layer_period": a.attn_every,
            "attn_layer_offset": a.attn_offset,
            "mamba_expand": a.ssm.expand, "mamba_d_state": a.ssm.d_state,
            "mamba_d_conv": a.ssm.d_conv,
            "mamba_dt_rank": a.ssm.rank(a.d_model),
            "vocab_size": a.vocab_size,
            "tie_word_embeddings": a.tie_embeddings,
            "rms_norm_eps": a.norm_eps}


def _tokens(n, seed=1):
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _ref_logits(n, seed=0):
    """The reference's logits at every position of ``_tokens(n)``."""
    rc = ref_cfg(CFG)
    params = REF.make_init(rc)(jax.random.PRNGKey(seed))
    x = REF._hidden_jit(rc, False)(params, jnp.asarray(_tokens(n)))
    return np.asarray(REF.head(rc, params, x))


def _served_logits(cfg, tokens, n_dec, seed=0):
    """The program's logits, in f32 from its own bf16 init (jitted, as the
    reference's is): the prompt's prefill (last position), then ``n_dec``
    teacher-forced decode steps."""
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    n = len(tokens)
    P = n - n_dec
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(
        tokens[None, :P])}, cache_len=n)
    out = [np.asarray(logits[0])]
    decode = jax.jit(model.decode_step)
    for pos in range(P, n - 1):
        logits, cache = decode(params, cache,
                               jnp.asarray(tokens[None, pos:pos + 1]),
                               jnp.int32(pos))
        out.append(np.asarray(logits[0]))
    return np.stack(out), P


def _worst_gap(cfg, n=40, n_dec=8):
    tokens = _tokens(n)
    got, P = _served_logits(cfg, tokens, n_dec)
    want = _ref_logits(n)[P - 1:n - 1]
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_program_init_follows_the_documented_key_schedule():
    """Bit for bit, both jitted (an eager init may round a weight's last
    bf16 bit the other way: XLA fuses the scale into the draw)."""
    key = jax.random.PRNGKey(7)
    got = jax.jit(build_model(CFG).init)(key)
    want = REF.make_init(ref_cfg(CFG))(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a.view(jnp.uint16)),
                                      np.asarray(b.view(jnp.uint16)))


def test_prefill_then_decode_matches_the_reference():
    """The prompt's prefill and 8 decode steps through the cache against
    the reference's full forward at each position."""
    assert _worst_gap(CFG) < TOL


@pytest.mark.parametrize("fault", ["rope_on", "no_inner_norms",
                                   "attention_at_offset_0"])
def test_planted_faults_fail_the_comparison(fault):
    bad = {"rope_on": dataclasses.replace(CFG, use_rope=True),
           "no_inner_norms": dataclasses.replace(
               CFG, ssm=dataclasses.replace(CFG.ssm, inner_norms=False)),
           "attention_at_offset_0": dataclasses.replace(CFG, attn_offset=0),
           }[fault]
    assert _worst_gap(bad) > 100 * TOL


def test_prefill_scan_equals_the_reference_recurrence():
    """The prefill's unrolled scan, over a length that is no multiple of
    its unrolling, against the reference's recurrence, layer for layer."""
    spec = S.MambaSpec(CFG.d_model, CFG.ssm, CFG.norm_eps)
    n = 3 * S.UNROLL + 5
    p = REF.make_init(ref_cfg(CFG))(jax.random.PRNGKey(3))["stack"]["l0_mamba"]
    p = jax.tree.map(lambda t: t[0].astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, n, CFG.d_model))
    got, cache = jax.jit(lambda p, x: S.mamba_prefill(p, spec, x))(p, x)
    want = jax.jit(lambda p, x: REF._mamba_fwd(REF.shapes(ref_cfg(CFG)),
                                               False, x, p))(p, x[0])
    # f32 both, the same recurrence; XLA may fuse or reorder a sum
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert cache["h"].shape == (1, CFG.ssm.d_state, spec.d_inner)


@pytest.mark.parametrize("heads,kv_heads", [(8, 1), (8, 2)],
                         ids=["mqa", "gqa"])
def test_blocked_prefill_attention_equals_attention_ref(heads, kv_heads):
    S_, hd = 2 * L.Q_BLOCK + 100, 32        # two whole blocks and a part
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, S_, heads, hd))
    k = jax.random.normal(ks[1], (2, S_, kv_heads, hd))
    v = jax.random.normal(ks[2], (2, S_, kv_heads, hd))
    got = jax.jit(L.attention_blocked)(q, k, v)
    want = jax.jit(L.attention_ref)(q, k, v)
    # each query's row is the same computation; f32 leaves only the
    # matmul's own tiling to differ
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_row_at_a_time_prefill_agrees_with_the_batched_prefill():
    """Each row of the engine's batch cache is the one-row prefill of its
    prompt, bit for bit, and the rows agree with one prefill of the whole
    batch."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, cache_len=48)
    rng = np.random.Generator(np.random.PCG64(2))
    prompt = rng.integers(0, cfg.vocab_size, (3, 32)).astype(np.int32)
    first, cache = engine.prefill({"tokens": prompt})
    whole = jax.jit(lambda p, b: model.prefill(p, b, cache_len=48))
    want_logits, want = whole(params, {"tokens": jnp.asarray(prompt)})
    got_logits = []
    for r in range(3):
        one_logits, one = whole(params,
                                {"tokens": jnp.asarray(prompt[r:r + 1])})
        got_logits.append(one_logits[0])
        assert int(first[r, 0]) == int(jnp.argmax(one_logits[0]))
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a)[:, r],
                                          np.asarray(b)[:, 0])
    # f32: the batch's [B*S, d] products are summed in another order than
    # one row's [S, d] ones, about 1e-7 of a value at the first layer,
    # and the layers carry it on: the worst leaf differs by 7.0e-6 of its
    # scale (6.3e-6 without the inner norms). 1e-4 of each leaf's scale
    # leaves that room fourteen times over, and a row written at another
    # row's place, or a state left at zero, differs by the leaf's own
    # scale.
    for g, w in zip(jax.tree.leaves(cache) + [jnp.stack(got_logits)],
                    jax.tree.leaves(want) + [want_logits]):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-4 * float(np.max(np.abs(w))))


@pytest.fixture
def _virtual_time(sim_clock):
    yield


def _host(state):
    """name -> host copy of every array leaf of a ServeApp state."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(
            {k: state[k] for k in ("params", "cache", "last_token")})[0]:
        out[jax.tree_util.keystr(path)] = np.array(x)
    toks = state["tokens_out"]
    out["tokens_out"] = np.concatenate(toks, axis=1) \
        if isinstance(toks, list) else np.array(toks)
    return out


def _same_bits(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        assert a[k].tobytes() == b[k].tobytes(), k


def test_hybrid_serve_suspended_and_resumed_through_the_service(
        _virtual_time, monkeypatch):
    """Mid-generation the service swaps a hybrid ServeApp out: its image
    holds every leaf (weights, K/V rows, f32 recurrent state, conv tails,
    tokens) bit for bit as the app held it at the pin, the resumed app
    starts from exactly that state, and it gives the uninterrupted token
    stream. The pin's span carries the bytes of each kind of state, and
    the prefill one span per row."""
    from repro.obs.telemetry import registry
    from repro.obs.trace import tracer
    n_tokens = 20

    def make(**kw):
        return ServeApp(CFG, batch=2, prompt_len=12, n_tokens=n_tokens,
                        cache_len=40, **kw)

    ref = make()
    ref.start(None, None)
    while not ref.is_done():
        time.sleep(0.02)
    ref.stop()
    ref_tokens = ref.checkpoint_state()["tokens_out"]

    pinned, restored = [], []
    capture, start = ServeApp._capture, ServeApp.start

    def pin(self):
        snap = capture(self)
        pinned.append(_host(snap))
        return snap

    def restart(self, ctx, restore_state):
        if restore_state is not None:
            restored.append(_host(restore_state))
        return start(self, ctx, restore_state)
    monkeypatch.setattr(ServeApp, "_capture", pin)
    monkeypatch.setattr(ServeApp, "start", restart)

    svc = CACSService({"snooze": SnoozeBackend(4)},
                      {"default": InMemoryStore()})
    try:
        cid = svc.submit(ASR(name="hybrid", n_vms=1, backend="snooze",
                             app_factory=lambda: make(token_delay_s=0.1),
                             policy=CheckpointPolicy(period_s=0)))
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        coord = svc.db.get(cid)
        while coord.app.generated < 5:
            time.sleep(0.02)
        svc.apps.suspend(cid)
        (at_pin,) = pinned
        kv = sum(x.nbytes for k, x in at_pin.items()
                 if k.startswith("['cache']") and "attn" in k)
        rec = sum(x.nbytes for k, x in at_pin.items()
                  if k.startswith("['cache']") and "mamba" in k)
        pin = tracer().spans(name="ckpt/pin")[-1]
        assert (pin.args["attn_bytes"], pin.args["ssm_bytes"]) == (kv, rec)
        assert registry().gauge("serve.cache_bytes.ssm").value == rec
        pre = [s for s in tracer().spans(name="serve/prefill")
               if s.args["tokens"] == 2 * 12][-1]
        rows = [s for s in tracer().spans(name="serve/prefill_row")
                if s.parent is pre]
        assert [s.args["row"] for s in rows] == [0, 1]
        assert 5 <= at_pin["tokens_out"].shape[1] < n_tokens
        kinds = {str(x.dtype) for k, x in at_pin.items()
                 if k.startswith("['cache']")}
        assert kinds == {"bfloat16", "float32"}
        _same_bits(_host(svc.ckpt.load(coord, svc.ckpt.latest(coord))),
                   at_pin)
        svc.apps.resume(cid)
        coord = svc.db.get(cid)
        while not coord.app.is_done():
            time.sleep(0.05)
        assert coord.app.restarts == 1
        (at_restore,) = restored
        _same_bits(at_restore, at_pin)
        np.testing.assert_array_equal(
            coord.app.checkpoint_state()["tokens_out"], ref_tokens)
    finally:
        svc.shutdown()

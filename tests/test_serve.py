"""Serving engine + CACS-hosted serving: suspend/resume mid-generation must
not change the generated token stream."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import InMemoryStore
from repro.clusters import SnoozeBackend
from repro.configs import get_config, reduced
from repro.core import ASR, CACSService, CheckpointPolicy, CoordState
from repro.models import build_model
from repro.obs.telemetry import registry
from repro.serve.engine import Engine, ServeApp
from repro.sim.simtime import active_clock

CFG = dataclasses.replace(reduced(get_config("repro-100m")), dtype="float32")


class _FlakyServe(ServeApp):
    """ServeApp whose decode raises on the step that would take the slot
    past ``fail_at`` tokens (delivered or still in flight)."""

    def __init__(self, *args, fail_at=4, **kwargs):
        super().__init__(*args, **kwargs)
        self._fail_at = fail_at

    def _build(self):
        super()._build()
        real = self.engine.decode

        def decode(cache, token, pos):
            if self._slot_count() >= self._fail_at:
                raise RuntimeError("chaos: device lost mid-decode")
            return real(cache, token, pos)
        self.engine.decode = decode


class _GatedServe(ServeApp):
    """ServeApp whose decode parks on a wall event while it holds the
    donated cache — reproduces the surrendered-slot window at will, from
    the step that would take the slot past ``gate_at`` tokens."""

    def __init__(self, *args, gate_at=2, **kwargs):
        super().__init__(*args, **kwargs)
        self._gate_at = gate_at
        self.entered = threading.Event()
        self.release = threading.Event()

    def _build(self):
        super()._build()
        real = self.engine.decode

        def decode(cache, token, pos):
            if self._slot_count() >= self._gate_at \
                    and not self.release.is_set():
                self.entered.set()
                self.release.wait(30)
            return real(cache, token, pos)
        self.engine.decode = decode


@pytest.fixture(autouse=True)
def _virtual_time(sim_clock):
    """ServeApp's token_delay_s / capture polls sleep on active_clock();
    riding the shared SimClock turns those delays into instant virtual
    jumps (the suspend-resume test no longer wall-sleeps ~2.4s)."""
    yield


def test_engine_generate_shapes():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, cache_len=48)
    toks = jnp.ones((2, 16), jnp.int32)
    out = engine.generate({"tokens": toks}, 8)
    assert out.shape == (2, 8)
    assert out.dtype == jnp.int32
    assert int(out.max()) < model.vocab_padded


def test_generate_deterministic():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    e1 = Engine(model, params, cache_len=48)
    e2 = Engine(model, params, cache_len=48)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, CFG.vocab_size, (1, 16)),
        jnp.int32)
    np.testing.assert_array_equal(np.asarray(e1.generate({"tokens": toks}, 8)),
                                  np.asarray(e2.generate({"tokens": toks}, 8)))


def test_serve_app_suspend_resume_token_stream_unchanged():
    """Job-swapping applied to inference: the interrupted stream equals the
    uninterrupted one."""
    n_tokens = 24
    ref = ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                   cache_len=40)
    ref.start(None, None)
    while not ref.is_done():
        time.sleep(0.02)
    ref.stop()
    ref_tokens = ref.checkpoint_state()["tokens_out"]

    backend = SnoozeBackend(4)
    svc = CACSService({"snooze": backend}, {"default": InMemoryStore()})
    try:
        asr = ASR(name="serve", n_vms=1, backend="snooze",
                  app_factory=lambda: ServeApp(CFG, batch=1, prompt_len=8,
                                               n_tokens=n_tokens,
                                               cache_len=40,
                                               token_delay_s=0.1),
                  policy=CheckpointPolicy(period_s=0))
        cid = svc.submit(asr)
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        coord = svc.db.get(cid)
        while coord.app.generated < 4:
            time.sleep(0.02)
        svc.apps.suspend(cid)
        gen_at_suspend = coord.app.generated
        assert gen_at_suspend < n_tokens
        svc.apps.resume(cid)
        coord = svc.db.get(cid)
        while not coord.app.is_done():
            time.sleep(0.05)
        out = coord.app.checkpoint_state()["tokens_out"]
        assert coord.app.restarts == 1
        np.testing.assert_array_equal(out[:, :ref_tokens.shape[1]],
                                      ref_tokens)
    finally:
        svc.shutdown()


def test_decode_failure_restores_cache_and_flips_health():
    """Regression: a decode exception used to leave the donated-cache slot
    None forever — every later capture (suspend, snapshot) deadlocked and
    healthy() stayed True on a dead loop."""
    before = registry().value("serve.decode_failures", 0.0)
    app = _FlakyServe(CFG, batch=1, prompt_len=8, n_tokens=24, cache_len=40,
                      fail_at=3)
    app.start(None, None)
    app._thread.join(timeout=30)
    assert not app._thread.is_alive(), "decode thread should have died"
    assert app.healthy() is False
    assert app.cache is not None, "donated slot must be restored on failure"
    # capture still works (swap-out after the fault), without deadlock
    state = app.checkpoint_state()
    assert state["generated"] == 3
    assert state["tokens_out"].shape == (1, 3)
    assert registry().value("serve.decode_failures", 0.0) == before + 1
    assert app.stop() is False


def test_capture_blocks_without_advancing_virtual_time(sim_clock,
                                                       monkeypatch):
    """Regression: _capture busy-polled ``clock.sleep(0.001)`` while a
    decode held the donated cache — on a SimClock each poll jumped virtual
    time forward, re-timing every pending deadline in the process. The
    capture thread must never sleep on the installed clock (spied on
    directly: daemons leaked by earlier tests may legitimately advance the
    shared clock, so a now()-didn't-move assertion would be flaky)."""
    app = _GatedServe(CFG, batch=1, prompt_len=8, n_tokens=24, cache_len=40,
                      gate_at=2)
    app.start(None, None)
    try:
        assert app.entered.wait(30), "decode never reached the gate"
        clock = active_clock()
        sleeper_idents = []
        real_sleep = clock.sleep

        def spy(dt):
            sleeper_idents.append(threading.get_ident())
            return real_sleep(dt)
        monkeypatch.setattr(clock, "sleep", spy)
        got = {}

        def grab():
            got["state"] = app.checkpoint_state()
        t = threading.Thread(target=grab, daemon=True)
        t.start()
        time.sleep(0.3)          # wall time: capture must still be pinned
        assert t.is_alive(), "capture returned during the donated window"
        app.release.set()
        t.join(timeout=30)
        assert not t.is_alive()
        assert got["state"]["generated"] >= 2
        assert t.ident not in sleeper_idents, \
            "capture slept on the installed clock during the donated window"
    finally:
        app.release.set()
        app.stop()


def test_stop_timeout_counts_leaked_decode_thread():
    """Regression: stop() joined with a timeout and returned regardless —
    a wedged decode thread leaked silently. It must be detected, counted
    in serve.stop_timeouts (with the last error as note) and reported."""
    before = registry().value("serve.stop_timeouts", 0.0)
    app = _GatedServe(CFG, batch=1, prompt_len=8, n_tokens=24, cache_len=40,
                      gate_at=2)
    app.start(None, None)
    try:
        assert app.entered.wait(30), "decode never reached the gate"
        leaked = app.stop(join_s=0.2)
        assert leaked is True
        assert registry().value("serve.stop_timeouts", 0.0) == before + 1
    finally:
        app.release.set()
        app._thread.join(timeout=30)
    assert not app._thread.is_alive()
    assert app.stop() is False


# ---- one decode step in flight ---------------------------------------------

HYBRID = reduced(get_config("jamba2-3b"))
BOTH = pytest.mark.parametrize("cfg", [CFG, HYBRID], ids=["dense", "hybrid"])


def _prompt(cfg, batch, prompt_len, seed=0):
    """The prompt a ServeApp of this seed draws."""
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.integers(0, cfg.vocab_size,
                        (batch, prompt_len)).astype(np.int32)


def _generate(app, n_tokens):
    """Engine.generate's tokens for the app's weights and prompt, on the
    app's own engine (its compiled steps)."""
    prompt = _prompt(app.cfg, app.batch, app.prompt_len, app.seed)
    return np.asarray(app.engine.generate({"tokens": jnp.asarray(prompt)},
                                          n_tokens))


def _run_alone(app, restore=None):
    app.start(None, restore)
    deadline = time.monotonic() + 120
    while not app.is_done():
        assert time.monotonic() < deadline, "app did not finish"
        time.sleep(0.01)
    assert app.stop() is False
    assert app.healthy()
    return np.concatenate(app.tokens_out, axis=1)


class _CountingServe(ServeApp):
    """ServeApp that records the slot's count at every dispatch."""

    def _build(self):
        super()._build()
        self.dispatched_at = []
        real = self.engine.decode

        def decode(cache, token, pos):
            self.dispatched_at.append(self._slot_count())
            return real(cache, token, pos)
        self.engine.decode = decode


class _PinningServe(ServeApp):
    """ServeApp that meets the test at the sync of each step in
    ``pin_at`` (slot counts): the step is in flight and its token, and the
    one before it, are not yet fetched. The test pins there."""

    def __init__(self, *args, pin_at=(), **kwargs):
        super().__init__(*args, **kwargs)
        self.pin_at = set(pin_at)
        self.meet = threading.Barrier(2, timeout=60)

    def _deliver(self, keep):
        if keep and self._slot_count() in self.pin_at:
            self.pin_at.discard(self._slot_count())
            self.meet.wait()             # the test may pin now
            self.meet.wait()             # the test has pinned
        super()._deliver(keep)


@BOTH
def test_pipelined_stream_equals_generate(cfg):
    """The served stream, one step in flight, is Engine.generate's bit for
    bit."""
    n_tokens = 10
    app = ServeApp(cfg, batch=2, prompt_len=8, n_tokens=n_tokens,
                   cache_len=24)
    got = _run_alone(app)
    assert got.shape == (2, n_tokens)
    np.testing.assert_array_equal(got, _generate(app, n_tokens))


@pytest.mark.parametrize("cfg,pin_at", [(CFG, (2, 5, 8, 11)),
                                        (HYBRID, (3, 10))],
                         ids=["dense", "hybrid"])
def test_pins_mid_stream_each_restore_to_the_uninterrupted_stream(cfg,
                                                                 pin_at):
    """Pins taken with a step in flight hold cache, last token, tokens and
    count of that one step: each image, restored through restart_from,
    decodes on to the uninterrupted stream. Each image's count is ahead of
    the public counter by the tokens not yet fetched. (Every restart
    compiles the decode anew, so the hybrid pins twice.)"""
    n_tokens = 12

    def make():
        return _PinningServe(cfg, batch=2, prompt_len=8, n_tokens=n_tokens,
                             cache_len=24, pin_at=pin_at)

    svc = CACSService({"snooze": SnoozeBackend(4)},
                      {"default": InMemoryStore()})
    try:
        cid = svc.submit(ASR(name="pins", n_vms=1, backend="snooze",
                             app_factory=make,
                             policy=CheckpointPolicy(
                                 period_s=0, keep_last=len(pin_at))))
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        coord = svc.db.get(cid)
        app = coord.app
        images = []
        for k in pin_at:
            app.meet.wait()
            public = app.generated
            step = svc.apps.checkpoint_now(cid)
            app.meet.wait()
            images.append((step, k, public))
        while not app.is_done():
            time.sleep(0.01)
        ref = _generate(app, n_tokens)
        np.testing.assert_array_equal(
            np.concatenate(app.tokens_out, axis=1), ref)
        for step, k, public in images:
            img = svc.ckpt.load(coord, step)
            g = int(img["generated"])
            assert g == k and g > public
            np.testing.assert_array_equal(img["tokens_out"], ref[:, :g])
            np.testing.assert_array_equal(np.asarray(img["last_token"]),
                                          ref[:, g - 1:g])
            svc.apps.restart_from(cid, step)
            while not app.is_done():
                time.sleep(0.01)
            np.testing.assert_array_equal(
                app.checkpoint_state()["tokens_out"], ref)
        assert app.restarts == len(pin_at)
        assert app.healthy()
    finally:
        svc.shutdown()


def test_generated_counts_only_delivered_tokens_within_budget():
    """The public counter counts tokens in host memory and never passes
    the budget; the loop never dispatches past it."""
    n_tokens = 12
    app = _CountingServe(CFG, batch=2, prompt_len=8, n_tokens=n_tokens,
                         cache_len=24)
    seen, bad = [], []
    done = threading.Event()

    def watch():
        while not done.is_set():
            g = app.generated
            toks = list(app.tokens_out)
            if g > n_tokens or len(toks) < g or not all(
                    isinstance(t, np.ndarray) for t in toks[:g]):
                bad.append((g, [type(t) for t in toks]))
            seen.append(g)
            time.sleep(0.0002)
    w = threading.Thread(target=watch, daemon=True)
    w.start()
    try:
        out = _run_alone(app)
    finally:
        done.set()
        w.join(timeout=10)
    assert not w.is_alive()
    assert not bad
    assert max(seen) <= n_tokens
    assert app.generated == n_tokens == out.shape[1]
    assert app._pending == []
    assert app.dispatched_at == list(range(1, n_tokens))


def test_stop_drains_the_step_in_flight():
    """stop() with a step in flight delivers its token: the count, the
    tokens and the slot's cache are of that step, and a restore from them
    decodes on to the uninterrupted stream."""
    n_tokens = 16
    app = _GatedServe(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                      cache_len=40, gate_at=6)
    app.start(None, None)
    try:
        assert app.entered.wait(30), "decode never reached the gate"
        # the step that takes the slot to 7 is being dispatched; token 6
        # is dispatched and not yet fetched
        assert app.generated == 5 and app._slot_count() == 6
        stopper = threading.Thread(target=app.stop, daemon=True)
        stopper.start()
        deadline = time.monotonic() + 30
        while not app._stop.is_set():
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        app.release.set()
    stopper.join(timeout=30)
    assert not stopper.is_alive() and not app._thread.is_alive()
    assert app.generated == 7 and app._pending == []
    tokens = np.concatenate(app.tokens_out, axis=1)
    ref = _generate(app, n_tokens)
    np.testing.assert_array_equal(tokens, ref[:, :7])
    state = app.checkpoint_state()
    assert state["generated"] == 7
    fresh = ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                     cache_len=40)
    np.testing.assert_array_equal(_run_alone(fresh, state), ref)


def test_decode_overlapped_counts_every_step_after_the_first():
    """In an unperturbed stream every decode step but the first is
    dispatched with the previous token still on the device; the span's
    ``overlapped`` arg says the same per step."""
    from repro.obs.trace import Tracer, use_tracer
    n_tokens = 10
    before = registry().value("serve.decode_overlapped", 0.0)
    with use_tracer(Tracer()) as tr:
        _run_alone(ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                            cache_len=24))
    steps = n_tokens - 1
    assert registry().value("serve.decode_overlapped", 0.0) - before \
        == steps - 1
    spans = sorted(tr.spans(name="serve/step"),
                   key=lambda s: s.args["generated"])
    assert [s.args["overlapped"] for s in spans] == \
        [False] + [True] * (steps - 1)

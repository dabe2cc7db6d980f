"""Trainer, optimizer, data pipeline: determinism + correctness."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import InMemoryStore, restore, save_checkpoint
from repro.configs import get_config, reduced
from repro.data.pipeline import TokenPipeline
from repro.obs.telemetry import registry
from repro.train import (AdamWConfig, TrainerApp, adamw_init, adamw_update,
                         lr_at)

CFG = dataclasses.replace(reduced(get_config("repro-100m")), dtype="float32")


@pytest.fixture(autouse=True)
def _virtual_time(sim_clock):
    """TrainerApp timing rides active_clock(); run the suite on the shared
    discrete-event clock like every other timed suite. The train thread
    itself never sleeps on the clock, so pacing is unchanged — only the
    service-side daemons/waits go virtual."""
    yield


def test_pipeline_deterministic_and_checkpointable():
    p1 = TokenPipeline(CFG, 4, 16, seed=3)
    batches = [p1.next() for _ in range(5)]
    # resume from state after 2 batches
    p2 = TokenPipeline(CFG, 4, 16, seed=3)
    p2.next(), p2.next()
    state = p2.state_dict()
    p3 = TokenPipeline(CFG, 4, 16, seed=99)   # wrong seed, fixed by state
    p3.load_state_dict(state)
    for i in range(2, 5):
        np.testing.assert_array_equal(p3.next()["tokens"],
                                      batches[i]["tokens"])


def test_pipeline_batches_cover_vocab_range():
    p = TokenPipeline(CFG, 4, 64)
    b = p.next()
    assert b["tokens"].min() >= 0 and b["tokens"].max() < CFG.vocab_size
    assert b["targets"][:, -1].max() == -1          # last target masked


def test_adamw_against_manual_step():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      grad_clip=0.0, warmup_steps=0, total_steps=10,
                      schedule="constant")
    params = {"w": jnp.asarray([[1.0, 2.0]])}      # 2D => decay-eligible
    grads = {"w": jnp.asarray([[0.5, -0.5]])}
    st = adamw_init(params)
    new_p, st2, _ = adamw_update(cfg, grads, st, params)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    expect = 1.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"])[0, 0], expect,
                               rtol=1e-5)
    assert int(st2["count"]) == 1


def test_grad_clip_caps_update_norm():
    cfg = AdamWConfig(grad_clip=1.0, weight_decay=0.0, warmup_steps=0,
                      schedule="constant")
    params = {"w": jnp.zeros((4, 4))}
    grads = {"w": jnp.full((4, 4), 100.0)}
    _, st, metrics = adamw_update(cfg, grads, adamw_init(params), params)
    assert float(metrics["grad_norm"]) > 100
    # effective m is built from clipped grads
    assert float(jnp.abs(st["m"]["w"]).max()) <= (1 - 0.9) * 1.0 + 1e-6


def test_lr_schedule_shapes():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                      schedule="cosine", min_lr_frac=0.1)
    lrs = [float(lr_at(cfg, jnp.int32(s))) for s in (0, 9, 10, 60, 109)]
    assert lrs[0] < 0.2                      # warmup start
    assert abs(lrs[2] - 1.0) < 0.06          # warmup end
    assert lrs[3] < lrs[2]                   # decaying
    assert abs(lrs[4] - 0.1) < 0.03          # floor


def test_loss_decreases_over_training():
    app = TrainerApp(CFG, global_batch=4, seq_len=32, n_steps=40,
                     opt=AdamWConfig(lr=1e-2, warmup_steps=3,
                                     total_steps=40))
    app.start(None, None)
    while not app.is_done():
        time.sleep(0.05)
    app.stop()
    first = np.mean(app.losses[:5])
    last = np.mean(app.losses[-5:])
    assert last < first - 0.5, f"no learning: {first:.3f} -> {last:.3f}"


def test_bit_exact_resume_through_checkpoint():
    straight = TrainerApp(CFG, global_batch=2, seq_len=32, n_steps=8)
    straight.start(None, None)
    while not straight.is_done():
        time.sleep(0.02)
    straight.stop()

    half = TrainerApp(CFG, global_batch=2, seq_len=32, n_steps=4)
    half.start(None, None)
    while not half.is_done():
        time.sleep(0.02)
    half.stop()
    store = InMemoryStore()
    save_checkpoint(store, "t", 4, half.checkpoint_state())
    snap, _ = restore(store, "t")

    resumed = TrainerApp(CFG, global_batch=2, seq_len=32, n_steps=8)
    resumed.start(None, snap)
    while not resumed.is_done():
        time.sleep(0.02)
    resumed.stop()
    assert resumed.losses[-1] == straight.losses[-1], "resume not bit-exact"


def test_failed_step_ends_loop_and_flips_health():
    """Regression: a raising train step used to kill the loop thread with
    is_done() never true, hanging every `while not app.is_done()` caller.
    It must be counted, flip healthy(), end is_done(), and a restart must
    retry the failed batch."""
    before = registry().value("trainer.step_failures", 0.0)
    app = TrainerApp(CFG, global_batch=2, seq_len=16, n_steps=6)
    real_step = app._train_step
    calls = []

    def step(state, batch):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real_step(state, batch)
    app._train_step = step
    app.start(None, None)
    app._thread.join(timeout=60)
    assert not app._thread.is_alive(), "loop must end on a failed step"
    assert app.current_step == 2
    assert app.is_done() and not app.healthy()
    assert registry().value("trainer.step_failures", 0.0) == before + 1
    assert app.pipeline.step == 2, "stream must rewind to the failed batch"
    app.stop()

    app.start(None, None)                   # in-place restart retries it
    app._thread.join(timeout=60)
    assert app.current_step == 6 and app.healthy()
    straight = TrainerApp(CFG, global_batch=2, seq_len=16, n_steps=6)
    straight.start(None, None)
    straight._thread.join(timeout=60)
    assert app.losses == straight.losses


def test_health_hook_detects_nan():
    app = TrainerApp(CFG, global_batch=2, seq_len=16, n_steps=5)
    app.start(None, None)
    while not app.is_done():
        time.sleep(0.02)
    app.stop()
    assert app.healthy()
    app.last_loss = float("nan")
    app.losses.append(float("nan"))
    assert not app.healthy()

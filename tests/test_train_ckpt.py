"""Real-pytree checkpoint data path: async device→host snapshots, device-
side qsnap encode, and device/host image interchange.

The contracts under test:
  * the staged snapshot path (snapshot_async → handle → writer thread)
    restores bit-exactly — params, opt_state and the data-iterator stream
    equal a never-suspended run (the lossless guard);
  * a device-encoded int8 image and a host-encoded int8 image of the same
    state are bit-for-bit interchangeable: same CAS digests (the second
    save dedups to zero uploads), same restored values, and either side's
    payload decodes through the other side's decoder.
"""
import dataclasses
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (AsyncCheckpointer, InMemoryStore, restore,
                        save_checkpoint)
from repro.ckpt.compression import decode as host_decode
from repro.ckpt.compression import encode as host_encode
from repro.ckpt.snapshot import ReadySnapshot, SnapshotHandle
from repro.clusters import SnoozeBackend
from repro.configs import get_config, reduced
from repro.core import (ASR, CACSService, CheckpointPolicy, CoordState,
                        SimulatedApp, snapshot_of)
from repro.kernels.qsnap import qsnap_dequantize
from repro.train.trainer import TrainerApp, encode_state_on_device

CFG = dataclasses.replace(reduced(get_config("repro-100m")), dtype="float32")


@pytest.fixture(autouse=True)
def _virtual_time(sim_clock):
    yield


def _run_to_done(app):
    app.start(None, None)
    while not app.is_done():
        time.sleep(0.02)
    app.stop()
    return app


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def test_async_snapshot_restore_bit_exact():
    """Lossless guard: the async device path restores the exact run —
    params, opt_state and token stream identical to never-suspended."""
    straight = _run_to_done(TrainerApp(CFG, global_batch=2, seq_len=32,
                                       n_steps=8))

    half = _run_to_done(TrainerApp(CFG, global_batch=2, seq_len=32,
                                   n_steps=4))
    handle = half.snapshot_async()             # staged: refs only
    assert isinstance(handle, SnapshotHandle)
    assert handle.step == 4
    assert len(half.ckpt_stalls) == 1
    store = InMemoryStore()
    ck = AsyncCheckpointer(store, "t", codec="raw")
    ck.save(4, handle)                         # resolved on writer thread
    ck.wait()
    ck.close()
    snap, _ = restore(store, "t")

    resumed = TrainerApp(CFG, global_batch=2, seq_len=32, n_steps=8)
    resumed.start(None, snap)
    while not resumed.is_done():
        time.sleep(0.02)
    resumed.stop()
    assert resumed.restarts == 1
    assert resumed.losses == straight.losses[4:], "stream diverged"
    assert _tree_equal(resumed.checkpoint_state()["state"],
                       straight.checkpoint_state()["state"])


def test_device_and_host_int8_images_interchange():
    """Device-encoded and host-encoded int8 images of the same state are
    byte-identical chunk-for-chunk: the second save dedups completely
    and both restore to the same values."""
    app = _run_to_done(TrainerApp(CFG, global_batch=2, seq_len=32,
                                  n_steps=2))
    state = app.checkpoint_state()
    store = InMemoryStore()
    man_host = save_checkpoint(store, "x", 1, state, codec="int8")
    man_dev = save_checkpoint(store, "x", 2, app.snapshot_async(codec="int8"),
                              codec="int8")
    # bit-for-bit interchange ⇒ every chunk of save 2 is a CAS hit
    assert man_dev.metadata["dedup"]["dedup_misses"] == 0
    assert man_dev.metadata["dedup"]["bytes_written"] == 0
    host_hashes = {c.hash for li in man_host.leaves.values()
                   for c in li.chunks}
    dev_hashes = {c.hash for li in man_dev.leaves.values()
                  for c in li.chunks}
    assert host_hashes == dev_hashes
    # a device-encoded image restores through the host decoder
    t1, _ = restore(store, "x", 1)
    t2, _ = restore(store, "x", 2)
    assert _tree_equal(t1, t2)
    # and the restored stream position survives the lossy image exactly
    assert int(t2["data"]["step"]) == 2


def test_host_encoded_payload_decodes_on_device():
    """The reverse direction: a host-codec int8 payload dequantizes via
    the Pallas kernel to the same values as the host decoder."""
    x = (np.random.default_rng(7).standard_normal(4096) * 3).astype(
        np.float32)
    payload = host_encode(x.tobytes(), np.float32, "int8")
    assert payload[:8] == b"QS01INT8"
    n, n_scales = struct.unpack("<qq", payload[8:24])
    scales = np.frombuffer(payload[24:24 + 4 * n_scales], np.float32)
    codes = np.frombuffer(payload[24 + 4 * n_scales:], np.int8)
    dev = qsnap_dequantize(jnp.asarray(codes), jnp.asarray(scales),
                           interpret=True)
    host = np.frombuffer(host_decode(payload, np.float32, "int8"),
                         np.float32)
    np.testing.assert_array_equal(np.asarray(dev)[:n], host)


def test_pre_encoded_leaves_reject_lossless_codec():
    """A lossy device-encoded payload must never satisfy a lossless
    image codec silently."""
    app = _run_to_done(TrainerApp(CFG, global_batch=2, seq_len=16,
                                  n_steps=1))
    encoded = encode_state_on_device(app.checkpoint_state()["state"])
    with pytest.raises(ValueError, match="cannot satisfy"):
        save_checkpoint(InMemoryStore(), "x", 1, {"state": encoded},
                        codec="raw")


def test_snapshot_of_wraps_legacy_apps():
    """Default adapter: apps without snapshot_async get a ReadySnapshot
    around the synchronous checkpoint_state — identical content."""
    app = SimulatedApp(n_iters=3, iter_time_s=0.0)
    app.start(None, None)
    while not app.is_done():
        time.sleep(0.01)
    app.stop()
    handle = snapshot_of(app)
    assert isinstance(handle, ReadySnapshot)
    direct = app.checkpoint_state()
    resolved = handle.resolve()
    assert resolved["iteration"] == direct["iteration"]
    np.testing.assert_array_equal(resolved["state"], direct["state"])
    assert handle.resolve() is resolved        # cached, not re-captured


def test_suspend_uses_swap_codec_and_resumes():
    """End-to-end control plane: policy.swap_codec routes the suspend
    image through the lossy device encode; periodic/explicit images stay
    on the lossless default; the job resumes from the int8 image."""
    backend = SnoozeBackend(4)
    svc = CACSService({"snooze": backend}, {"default": InMemoryStore()})
    try:
        asr = ASR(name="train", n_vms=1, backend="snooze",
                  app_factory=lambda: TrainerApp(CFG, global_batch=2,
                                                 seq_len=16, n_steps=200),
                  policy=CheckpointPolicy(period_s=0, codec="raw",
                                          swap_codec="int8"))
        cid = svc.submit(asr)
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        coord = svc.db.get(cid)
        while coord.app.current_step < 1:
            time.sleep(0.02)
        ckpt_step = svc.apps.checkpoint_now(cid)     # lossless image
        svc.apps.suspend(cid)                        # lossy swap-out image
        suspend_step = ckpt_step + 1
        assert svc.apps.ckpt.image_info(coord, ckpt_step)["codec"] == "raw"
        info = svc.apps.ckpt.image_info(coord, suspend_step)
        assert info["codec"] == "int8"
        assert info["metadata"]["suspend"] == "user"
        svc.apps.resume(cid)
        coord = svc.db.get(cid)
        resumed_from = coord.app.current_step
        while coord.app.current_step < resumed_from + 2:
            time.sleep(0.02)
        assert coord.app.restarts == 1
        assert coord.app.healthy()
    finally:
        svc.shutdown()


def test_no_periodic_image_lands_after_the_swap_out_image():
    """Regression: a periodic save that started while a suspend wrote its
    swap-out image committed a newer lossless image of the same state, so
    resume restored that one instead of the swap-out image."""
    svc = CACSService({"snooze": SnoozeBackend(4)},
                      {"default": InMemoryStore(latency_s=0.05)})
    try:
        asr = ASR(name="train", n_vms=1, backend="snooze",
                  app_factory=lambda: TrainerApp(CFG, global_batch=2,
                                                 seq_len=16, n_steps=2),
                  policy=CheckpointPolicy(period_s=0.05, codec="raw",
                                          keep_last=0, swap_codec="int8"))
        cid = svc.submit(asr)
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        coord = svc.db.get(cid)
        while not coord.app.is_done():
            time.sleep(0.02)
        svc.apps.suspend(cid)
        time.sleep(0.5)                      # let a racing save get queued
        svc.apps.ckpt.wait(coord)
        newest = svc.list_checkpoints(cid)[-1]
        assert svc.get_checkpoint(cid, newest)["metadata"]["suspend"] \
            == "user"
        svc.apps.resume(cid)
        assert svc.db.get(cid).state == CoordState.RUNNING
    finally:
        svc.shutdown()

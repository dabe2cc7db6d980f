"""Suspend and resume cycles of a training job, back to back.

Set-up submits a ``TrainerApp`` as the train driver does, with periodic
saves off and ``swap_codec="int8"`` (the device ``qsnap`` encode), drives
it through its first steps (the model-step check reads them) and through
one whole cycle, so that nothing compiles in the window. A cycle runs
from the ``suspend`` call to the end of the first train step after
``resume``; the window runs cycles until ``--seconds`` have passed and
counts those that ended inside it.

After the window the loop is stopped and one more cycle is checked: each
float leaf's image payload must equal the benchmark's own ``QS01``
encoding of the pinned state, and the resumed state must be its decode.
"""
from __future__ import annotations

import gc
import time

from chipbench import checks, counts, qs01
from chipbench.harness import (BenchFailure, Ctx, TrainProbe,
                               check_train_steps, make_service, mark,
                               live_device_bytes, trainer_factory,
                               wait_for)


def run(ctx: Ctx) -> None:
    from repro.ckpt.layout import leaf_items
    from repro.ckpt.reader import load_manifest
    from repro.core import ASR, CheckpointPolicy, CoordState
    from repro.obs.telemetry import registry

    wl = ctx.workload
    probe = TrainProbe(wl["optimizer"]["b1"], wl["check_steps"])
    streams = []
    svc = make_service()
    health = checks.Health(registry())
    try:
        policy = CheckpointPolicy(period_s=0, codec="raw", keep_last=2,
                                  swap_codec=wl["swap_codec"])
        cid = svc.submit(ASR(name=wl["name"], n_vms=1, backend="local",
                             app_factory=trainer_factory(ctx, probe, streams),
                             policy=policy))
        app = svc.wait_for_state(cid, CoordState.RUNNING, 900).app
        coord = svc.db.get(cid)
        wait_for(lambda: app.current_step > wl["check_steps"], "first steps",
                 app=app)

        def cycle() -> float:
            t = time.perf_counter()
            with mark("suspend"):
                svc.apps.suspend(cid)
            with mark("resume"):
                svc.apps.resume(cid)
            if svc.db.get(cid).state != CoordState.RUNNING:
                raise BenchFailure(f"resume left the job "
                                   f"{svc.db.get(cid).state.value}")
            s = app.current_step
            with mark("first_step"):
                wait_for(lambda: app.current_step > s, "step after resume",
                         app=app, poll_s=0.0005)
            return time.perf_counter() - t

        cycle()
        state = app.checkpoint_state()["state"]
        floats = [(x.shape, x.dtype) for _, x in leaf_items(state)
                  if hasattr(x, "dtype") and x.dtype.kind in "fV"]
        del state

        ctx.begin_window()
        t_end = ctx.window_t0 + ctx.seconds
        done, started = [], 0
        while time.perf_counter() < t_end:
            started += 1
            d = cycle()
            if time.perf_counter() <= t_end:
                done.append(d)
        ctx.end_window()
        if not done:
            raise BenchFailure("no swap cycle completed in the window")
        ctx.e2e["swap_cycle_s"] = sum(done) / len(done)
        ctx.attempted = started
        ctx.record.update(
            swap_cycle_s=ctx.e2e["swap_cycle_s"],
            train_flops_per_step=wl["batch"] * wl["seq_len"]
            * counts.train_flops_per_token(ctx.config, wl["seq_len"]),
            spans={n: ctx.spans_in_window(n)
                   for n in ("ckpt/pin", "ckpt/save", "ckpt/restore")},
            qsnap_bytes=started * counts.qsnap_encode_bytes(floats))
        app.stop()
        ctx.read_peak()
        ctx.failed += int(not app.healthy())

        pre = checks.host_tree({"state": app.checkpoint_state()["state"]})
        svc.apps.suspend(cid)
        step = svc.list_checkpoints(cid)[-1]
        store = svc.ckpt.store(policy.store)
        man = load_manifest(store, coord.ckpt_prefix, step)
        bad_payloads = 0
        expected = {}
        for name, li in man.leaves.items():
            x = pre.get(name)
            if x is None or x.dtype.kind not in "fV":
                continue
            expected[name] = qs01.decode(x)
            got = b"".join(store.get(c.key) for c in li.chunks)
            bad_payloads += int(got != qs01.encode(x))
        ctx.compare("payloads_differing", bad_payloads
                    + abs(len(expected) - len(floats)))
        probe.capture = got = {}
        svc.apps.resume(cid)
        wait_for(lambda: bool(got), "the resumed state", app=app)
        app.stop()
        want = {k: expected.get(k, v) for k, v in pre.items()}
        ctx.compare("restored_off_decode", sum(
            int(not checks.trees_equal({k: got[k]}, {k: want[k]}))
            for k in want.keys() & got.keys()) + len(got.keys() ^ want.keys()))
        del pre, got, expected, want
        losses = list(app.losses)
        probe.app = None
        del app, coord
        svc.delete_coordinator(cid)
    finally:
        svc.shutdown()
    rises = health.rises()
    ctx.failed += int(sum(rises.values()))
    if rises:
        ctx.record["health_rises"] = rises
    del svc
    gc.collect()
    ctx.log(f"left on the device: {live_device_bytes()} bytes")
    check_train_steps(ctx, losses, probe)

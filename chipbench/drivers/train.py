"""Closed-loop training through the service, with or without saves.

Set-up submits a ``TrainerApp`` to ``CACSService`` over ``LocalBackend(1)``,
fed by the benchmark's own token stream, and drives it through its first
steps (the model-step check reads them). The window then runs for
``--seconds`` while the harness asks the service for an async lossless
save (``trigger_checkpoint``, the path the periodic daemon takes) every
``period_s`` from ``first_s``, so that every run has its saves at the same
points of the window.

After the window the loop is stopped, one more save is restored through
the service and compared bit for bit with the live state, the job and the
service are torn down, and the plain reference follows the first steps.
"""
from __future__ import annotations

import gc

from chipbench import checks, counts
from chipbench.harness import (BenchFailure, Ctx, TrainProbe,
                               check_train_steps, live_device_bytes,
                               log_saves, make_service, save_window,
                               trainer_factory, wait_for, window_rate)


def run(ctx: Ctx) -> None:
    from repro.core import ASR, CheckpointPolicy, CoordState
    from repro.obs.telemetry import registry

    wl, cfg = ctx.workload, ctx.config
    saves = wl.get("saves")
    tokens_per_step = wl["batch"] * wl["seq_len"]
    probe = TrainProbe(wl["optimizer"]["b1"], wl["check_steps"])
    streams = []
    svc = make_service()
    health = checks.Health(registry())
    try:
        policy = CheckpointPolicy(
            period_s=0, codec=saves["codec"] if saves else "raw",
            keep_last=saves["keep_last"] if saves else 2)
        cid = svc.submit(ASR(name=wl["name"], n_vms=1, backend="local",
                             app_factory=trainer_factory(ctx, probe, streams),
                             policy=policy))
        app = svc.wait_for_state(cid, CoordState.RUNNING, 900).app
        coord = svc.db.get(cid)
        wait_for(lambda: app.current_step > wl["check_steps"], "first steps",
                 app=app)
        stream = streams[-1]
        n_times0 = len(app.step_times)
        state_bytes = counts.tree_bytes(app.checkpoint_state()["state"])
        ctx.log(f"train state: {state_bytes} bytes")

        n_saves = len(save_window(ctx, svc, coord, app, saves))

        stamps = stream.stamps_between(ctx.window_t0, ctx.window_t1)
        rate = window_rate(stamps, tokens_per_step)
        if rate is None:
            raise BenchFailure("no train step completed in the window")
        steps = len(stamps) - 1
        ctx.e2e["train_tokens_per_s"] = rate
        ctx.attempted = steps + n_saves
        step_times = app.step_times[n_times0:n_times0 + steps]
        app.stop()
        ctx.read_peak()
        err = svc.ckpt.wait(coord, strict=False)
        ctx.failed += int(err is not None) + int(not app.healthy())

        ctx.record.update(
            train_tokens_per_s=rate, step_times=step_times,
            train_flops_per_token=counts.train_flops_per_token(
                cfg, wl["seq_len"]),
            spans={n: ctx.spans_in_window(n)
                   for n in ("ckpt/pin", "ckpt/save")})
        log_saves(ctx)

        if saves:
            step = svc.trigger_checkpoint(cid, blocking=True)
            restored = svc.ckpt.load(coord, step)["state"]
            live = app.checkpoint_state()["state"]
            ctx.compare("image_leaves_differing",
                        checks.leaves_differing(restored, live))
            del restored, live
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

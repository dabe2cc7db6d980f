"""Batched greedy decoding through the service, with periodic snapshots.

Set-up submits a ``ServeApp`` (its weights and prompts from the seed) to
``CACSService`` over ``LocalBackend(1)``, waits out the prefill and the
first decode steps, and pins the state once, which compiles the pin's
on-device copy of the KV cache; the window starts a fixed number of
tokens into the generation. In the window a harness thread watches the
server's public ``generated`` counter (sleeping 0.2 ms between reads) and
stamps each change: the gaps between stamps are the token gaps. The
harness asks the service for an async snapshot every ``period_s`` from
``first_s``.

After the window the decode loop is stopped, the window's last snapshot
is restored through the service and compared bit for bit with the live
state as of its pin, the job and the service are torn down, and the plain
reference reads, over a sample of the requests drawn from the seed, the
logit gap of every token the server gave them.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import checks, configs, counts, program, traffic
from chipbench.harness import (BenchFailure, Ctx, live_device_bytes,
                               log_saves, make_service, save_window,
                               wait_for)

POLL_S = 0.0002


class Watcher(threading.Thread):
    """Stamps every change of the server's ``generated`` counter."""

    def __init__(self, app):
        super().__init__(daemon=True)
        self.app = app
        self.stamps = []                  # (perf_counter, generated)
        self.stop_ev = threading.Event()

    def run(self):
        last = self.app.generated
        while not self.stop_ev.is_set():
            g = self.app.generated
            if g != last:
                self.stamps.append((time.perf_counter(), g))
                last = g
            time.sleep(POLL_S)


def gaps_between(stamps, t0, t1):
    """Gaps between consecutive decode steps stamped in [t0, t1]; a stamp
    that covers k steps gives k gaps of equal length."""
    inside = [(t, g) for t, g in stamps if t0 <= t <= t1]
    gaps = []
    for (ta, ga), (tb, gb) in zip(inside, inside[1:]):
        gaps += [(tb - ta) / (gb - ga)] * (gb - ga)
    steps = inside[-1][1] - inside[0][1] if inside else 0
    span = inside[-1][0] - inside[0][0] if inside else 0.0
    return gaps, steps, span


def run(ctx: Ctx) -> None:
    from repro.core import ASR, CheckpointPolicy, CoordState
    from repro.obs.telemetry import registry
    from repro.serve.engine import ServeApp

    wl, cfg = ctx.workload, ctx.config
    saves = wl.get("saves")
    arch = program.arch(cfg)

    def make():
        return ServeApp(arch, batch=wl["batch"], prompt_len=wl["prompt_len"],
                        n_tokens=wl["n_tokens"], cache_len=wl["cache_len"],
                        seed=ctx.seed)

    svc = make_service()
    health = checks.Health(registry())
    try:
        policy = CheckpointPolicy(
            period_s=0, codec=saves["codec"] if saves else "raw",
            keep_last=saves["keep_last"] if saves else 2)
        cid = svc.submit(ASR(name=wl["name"], n_vms=1, backend="local",
                             app_factory=make, policy=policy))
        app = svc.wait_for_state(cid, CoordState.RUNNING, 900).app
        coord = svc.db.get(cid)
        wait_for(lambda: app.generated >= wl.get("warm_tokens", 4),
                 "first decode steps", app=app)
        if saves:
            app.checkpoint_state()       # compiles the pin's cache copy
        watcher = Watcher(app)
        watcher.start()
        ctx.log(f"generated before the window: {app.generated}")

        save_steps = save_window(ctx, svc, coord, app, saves)
        watcher.stop_ev.set()
        watcher.join(timeout=10)
        ctx.log(f"generated at the window's end: {app.generated}")

        gaps, steps, span = gaps_between(watcher.stamps, ctx.window_t0,
                                         ctx.window_t1)
        if steps < 2:
            raise BenchFailure("no decode step completed in the window")
        ctx.e2e["token_gap_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
        ctx.e2e["decode_tokens_per_s"] = wl["batch"] * steps / span
        ctx.attempted = steps + len(save_steps)
        done_early = app.is_done()
        app.stop()
        ctx.read_peak()
        err = svc.ckpt.wait(coord, strict=False)
        ctx.failed += int(err is not None) + int(not app.healthy()) \
            + int(done_early)
        inside = [g for t, g in watcher.stamps
                  if ctx.window_t0 <= t <= ctx.window_t1]
        filled = wl["prompt_len"] + (inside[0] + inside[-1]) / 2
        ctx.record.update(
            gaps=gaps,
            decode_cost=counts.decode_step_cost(cfg, wl["batch"], filled),
            spans={n: ctx.spans_in_window(n)
                   for n in ("ckpt/pin", "ckpt/save")})
        log_saves(ctx)

        if save_steps:
            served = image_vs_live(ctx, svc, coord, save_steps[-1], app)
        else:
            served = np.concatenate(app.tokens_out, axis=1)
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
    ctx.record["served"] = served
    ctx.compare("token_logit_gap", max_token_gap(ctx, served))
    ctx.log("reference done")


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(
        np.ascontiguousarray(a).view(np.uint8),
        np.ascontiguousarray(b).view(np.uint8))


def image_vs_live(ctx: Ctx, svc, coord, step, app) -> np.ndarray:
    """Compare the window's last snapshot image with what the server held
    when it was pinned, and return the served tokens.

    ``image_leaves_differing`` counts the leaves of the image that differ
    in any bit from the live state as of the pin: the weights, unchanged
    since; the tokens served up to the pin; and the KV cache, equal to the
    live one over the positions filled at the pin and zero beyond (the
    cache is zero-padded and each decode step writes one position). The
    weights leave the chip first, so that the image can be restored
    through the service onto the chip beside the live cache; their host
    copies are the ones the save's device-to-host copy left on each
    array's shard, so they cost the host no more memory."""
    import jax
    wl = ctx.workload
    params = {}
    for k, x in checks.path_items(app.params).items():
        params[k] = np.asarray(x.addressable_shards[0].data)
        x.delete()
    cache = checks.path_items(app.cache)
    tokens = np.concatenate(app.tokens_out, axis=1)
    img = svc.ckpt.load(coord, step)
    g0 = int(img["generated"])
    filled = wl["prompt_len"] + g0 - 1

    got = checks.path_items(img["params"])
    bad = len(got.keys() ^ params.keys())
    for k in got.keys() & params.keys():
        bad += int(not same_bits(np.asarray(got[k]), params[k]))
        got[k].delete()
    got = checks.path_items(img["cache"])
    bad += len(got.keys() ^ cache.keys())
    for k in got.keys() & cache.keys():
        a, b = got[k], cache[k]
        if a.shape != b.shape or a.shape.count(wl["cache_len"]) != 1:
            bad += 1
            continue
        ax = a.shape.index(wl["cache_len"])
        head = (slice(None),) * ax + (slice(0, filled),)
        tail = (slice(None),) * ax + (slice(filled, None),)
        bad += int(checks.leaves_differing(a[head], b[head]) > 0
                   or bool(jax.numpy.any(a[tail] != 0)))
    bad += int(not same_bits(np.asarray(img["tokens_out"]),
                             tokens[:, :g0]))
    bad += int(not same_bits(np.asarray(img["last_token"]),
                             tokens[:, g0 - 1:g0]))
    del img, got
    ctx.compare("image_leaves_differing", bad)
    ctx.log(f"image of step {step} checked: pinned at {g0} tokens")
    return tokens


def sample_rows(seed: int, batch: int, k: int):
    rng = np.random.Generator(np.random.PCG64([seed, 1]))
    return sorted(rng.choice(batch, size=min(k, batch), replace=False))


def max_token_gap(ctx: Ctx, served: np.ndarray, lowp: bool = False) -> float:
    """The widest gap, over a sample of the requests drawn from the seed
    and every token served to them, by which a served token's reference
    logit lies below the reference's best. With ``lowp`` the control's
    first token at each position stands in for the served one."""
    import jax
    wl, cfg = ctx.workload, ctx.config
    ref = configs.reference(cfg["reference"])
    params = ref.make_init(cfg)(jax.random.PRNGKey(ctx.seed))
    prompts = traffic.prompts(ctx.seed, wl["batch"], wl["prompt_len"],
                              cfg["vocab_size"])
    worst = 0.0
    for r in sample_rows(ctx.seed, wl["batch"], wl.get("check_rows", 2)):
        targets = None
        if lowp:
            _, targets = ref.token_gaps(cfg, params, prompts[r], served[r],
                                        lowp=True)
        gaps, _ = ref.token_gaps(cfg, params, prompts[r], served[r],
                                 targets=targets)
        worst = max(worst, float(gaps.max()))
    return worst

"""Batched greedy decoding of a Jamba hybrid through the service, with
periodic snapshots, and the window's snapshot resumed.

Set-up, window and token gaps are the ``serve`` driver's (its
``Watcher``, ``gaps_between`` and the reference's ``max_token_gap`` over
``sample_rows``): set-up submits a ``ServeApp`` (weights and prompts from
the seed) to ``CACSService`` over ``LocalBackend(1)``, waits out its
row-at-a-time prefill and the first decode steps, and pins the state once;
in the window the harness asks for an async snapshot every ``period_s``
from ``first_s``. The app's cache holds two kinds of state: K/V rows of
the attention layers, filled position by position, and the Mamba layers'
recurrent state, rewritten by every decode step.

After the window the decode loop is stopped and the window's last
snapshot is checked twice:

- ``image_leaves_differing``: the image against the live state for the
  weights, the tokens served up to the pin and the K/V rows (equal over
  the positions filled at the pin, zero beyond). The recurrent state
  cannot be checked so: the live one has moved on since the pin.
- ``resume_differing``: the live weights and cache leave the chip, the
  image is restored through the service (``restart_from``: the load and
  start a swap-in takes) into a fresh ``ServeApp``, which decodes
  ``resume_tokens`` tokens. Counted are the tokens, and the K/V rows those
  steps write, that differ in any bit from the live run's at the same
  positions: a recurrent state restored wrong shows in both.

Then the plain reference reads the logit gap of every token served to a
sample of the requests (``token_logit_gap``).
"""
from __future__ import annotations

import gc
from typing import Any, Dict

import numpy as np

from chipbench import checks, configs, hybrid_counts
from chipbench.harness import (BenchFailure, Ctx, live_device_bytes,
                               log_saves, make_service, save_window,
                               wait_for)

_serve = configs.driver("serve")
Watcher = _serve.Watcher
gaps_between = _serve.gaps_between
sample_rows = _serve.sample_rows
max_token_gap = _serve.max_token_gap        # the control reads it here
same_bits = _serve.same_bits


def arch(cfg: Dict[str, Any]):
    """The program's ArchConfig for a Jamba configuration file."""
    from repro.configs.base import ArchConfig, SSMConfig
    if cfg.get("hidden_act") != "silu" or cfg.get("num_experts", 1) != 1 \
            or not cfg.get("mamba_conv_bias") or cfg.get("mamba_proj_bias"):
        raise ValueError(f"{cfg['name']}: only the dense Jamba block (SwiGLU "
                         "MLP, conv bias, no projection bias) is driven")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_act="swiglu", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        attn_every=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"], use_rope=False,
        ssm=SSMConfig(d_state=cfg["mamba_d_state"],
                      d_conv=cfg["mamba_d_conv"],
                      expand=cfg["mamba_expand"],
                      dt_rank=cfg["mamba_dt_rank"], inner_norms=True),
        subquadratic=True)


def prefill_span(rows: int) -> Dict[str, float]:
    """The set-up prefill (``serve/prefill``) in wall seconds, and how many
    ``serve/prefill_row`` spans it held."""
    from repro.obs.trace import tracer
    from repro.sim.simtime import active_clock
    scale = active_clock().scale
    spans = tracer().spans(name="serve/prefill")
    if not spans:
        raise BenchFailure("no serve/prefill span")
    sp = spans[-1]
    kids = [s for s in tracer().spans(name="serve/prefill_row")
            if s.parent is sp]
    if len(kids) != rows:
        raise BenchFailure(f"prefill spans: {len(kids)} rows of {rows}")
    return {"dur_s": sp.duration * scale, "rows": len(kids),
            "tokens": int(sp.args["tokens"])}


def run(ctx: Ctx) -> None:
    from repro.core import ASR, CheckpointPolicy, CoordState
    from repro.obs.telemetry import registry
    from repro.serve.engine import ServeApp

    wl, cfg = ctx.workload, ctx.config
    saves = wl["saves"]
    model_cfg = arch(cfg)
    budget = {"n_tokens": wl["n_tokens"]}

    def make():
        return ServeApp(model_cfg, batch=wl["batch"],
                        prompt_len=wl["prompt_len"],
                        n_tokens=budget["n_tokens"],
                        cache_len=wl["cache_len"], seed=ctx.seed)

    svc = make_service()
    health = checks.Health(registry())
    try:
        policy = CheckpointPolicy(period_s=0, codec=saves["codec"],
                                  keep_last=saves["keep_last"])
        cid = svc.submit(ASR(name=wl["name"], n_vms=1, backend="local",
                             app_factory=make, policy=policy))
        app = svc.wait_for_state(cid, CoordState.RUNNING, 900).app
        coord = svc.db.get(cid)
        wait_for(lambda: app.generated >= wl["warm_tokens"],
                 "prefill and first decode steps", app=app)
        app.checkpoint_state()           # compiles the pin's cache copy
        prefill = prefill_span(wl["batch"])
        ctx.log(f"prefill: {prefill['dur_s']:.2f}s for {prefill['tokens']} "
                f"tokens in {prefill['rows']} rows")
        watcher = Watcher(app)
        watcher.start()
        ctx.log(f"generated before the window: {app.generated}")

        save_steps = save_window(ctx, svc, coord, app, saves)
        watcher.stop_ev.set()
        watcher.join(timeout=10)
        app.stop()
        inside = [g for t, g in watcher.stamps
                  if ctx.window_t0 <= t <= ctx.window_t1]
        ctx.log(f"generated at the window's end: {inside[-1:]}")

        gaps, steps, span = gaps_between(watcher.stamps, ctx.window_t0,
                                         ctx.window_t1)
        if steps < 2:
            raise BenchFailure("no decode step completed in the window")
        if not save_steps:
            raise BenchFailure("no snapshot in the window")
        ctx.e2e["token_gap_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
        ctx.e2e["decode_tokens_per_s"] = wl["batch"] * steps / span
        ctx.attempted = steps + len(save_steps)
        # finished inside the window; a traced run's decode goes on while
        # the profile is read after it
        done_early = inside[-1] >= budget["n_tokens"]
        ctx.read_peak()
        err = svc.ckpt.wait(coord, strict=False)
        ctx.failed += int(err is not None) + int(not app.healthy()) \
            + int(done_early)
        filled = wl["prompt_len"] + (inside[0] + inside[-1]) / 2
        ctx.record.update(
            gaps=gaps, decode_steps=steps,
            decode_cost=hybrid_counts.decode_step_cost(cfg, wl["batch"],
                                                       filled),
            prefill=prefill,
            prefill_flops=hybrid_counts.prefill_flops(
                cfg, prefill["rows"], wl["prompt_len"]),
            spans={n: ctx.spans_in_window(n)
                   for n in ("ckpt/pin", "ckpt/save")})
        log_saves(ctx)

        live = image_vs_live(ctx, svc, coord, save_steps[-1], app)
        del app
        resume_vs_live(ctx, svc, cid, save_steps[-1], make, budget, live)
        served = live["tokens"]
        del coord, live
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


def _kv_axis(shape, cache_len: int):
    """The position axis of a K/V leaf; None for a recurrent leaf."""
    return shape.index(cache_len) if shape.count(cache_len) == 1 else None


def image_vs_live(ctx: Ctx, svc, coord, step, app) -> Dict[str, Any]:
    """Compare the window's last snapshot image with what the server held
    when it was pinned (``image_leaves_differing``), then take the live
    state off the chip. Returns what the resume is compared with: the
    served tokens, the image's ``generated`` and, for each K/V leaf, the
    live rows at the positions the resume's steps write."""
    import jax
    wl = ctx.workload
    n = wl["resume_tokens"]
    params = {}
    for k, x in checks.path_items(app.params).items():
        params[k] = np.asarray(x.addressable_shards[0].data)
        x.delete()
    cache = checks.path_items(app.cache)
    tokens = np.concatenate(app.tokens_out, axis=1)
    img = svc.ckpt.load(coord, step)
    g0 = int(img["generated"])
    filled = wl["prompt_len"] + g0 - 1
    if tokens.shape[1] < g0 + n:
        raise BenchFailure(f"the live run stopped at {tokens.shape[1]} "
                           f"tokens, before {g0 + n}")

    got = checks.path_items(img["params"])
    bad = len(got.keys() ^ params.keys())
    for k in got.keys() & params.keys():
        bad += int(not same_bits(np.asarray(got[k]), params[k]))
        got[k].delete()
    del params
    got = checks.path_items(img["cache"])
    bad += len(got.keys() ^ cache.keys())
    rows = {}
    for k in got.keys() & cache.keys():
        a, b = got[k], cache[k]
        if a.shape != b.shape or a.dtype != b.dtype:
            bad += 1
            continue
        ax = _kv_axis(a.shape, wl["cache_len"])
        if ax is None:
            continue                # recurrent state: checked by the resume
        head = (slice(None),) * ax + (slice(0, filled),)
        tail = (slice(None),) * ax + (slice(filled, None),)
        bad += int(checks.leaves_differing(a[head], b[head]) > 0
                   or bool(jax.numpy.any(a[tail] != 0)))
        rows[k] = np.asarray(jax.lax.slice_in_dim(b, filled, filled + n,
                                                  axis=ax))
    bad += int(not same_bits(np.asarray(img["tokens_out"]),
                             tokens[:, :g0]))
    bad += int(not same_bits(np.asarray(img["last_token"]),
                             tokens[:, g0 - 1:g0]))
    for x in cache.values():
        x.delete()
    del img, got, cache
    ctx.compare("image_leaves_differing", bad)
    ctx.log(f"image of step {step} checked: pinned at {g0} tokens")
    return {"tokens": tokens, "g0": g0, "filled": filled, "kv_rows": rows}


def resume_vs_live(ctx: Ctx, svc, cid, step, make, budget,
                   live: Dict[str, Any]) -> None:
    """Restore the image of ``step`` through the service into a fresh
    server, let it decode ``resume_tokens`` tokens and count
    (``resume_differing``) the tokens and the K/V rows of those steps that
    differ in any bit from the live run's."""
    import jax
    wl = ctx.workload
    n, g0, lo = wl["resume_tokens"], live["g0"], live["filled"]
    budget["n_tokens"] = g0 + n
    coord = svc.db.get(cid)
    fresh = make()
    coord.app = fresh              # the restart brings this one up
    svc.apps.restart_from(cid, step)
    wait_for(lambda: fresh.is_done(), "resumed decode", app=fresh)
    fresh.stop()
    toks = np.concatenate(fresh.tokens_out, axis=1)
    bad = int(np.sum(toks[:, g0:g0 + n] != live["tokens"][:, g0:g0 + n])) \
        + abs(toks.shape[1] - (g0 + n)) * wl["batch"]
    cache = checks.path_items(fresh.cache)
    for k, want in live["kv_rows"].items():
        ax = _kv_axis(cache[k].shape, wl["cache_len"])
        got = np.asarray(jax.lax.slice_in_dim(cache[k], lo, lo + n, axis=ax))
        # a row is one position of one sequence in one layer: every
        # index but the last two (heads, head_dim)
        g, w = (np.moveaxis(np.ascontiguousarray(x).view(np.uint8), ax, 0)
                for x in (got, want))
        bad += int(np.sum(np.any(g != w, axis=(-1, -2))))
    del cache, fresh
    ctx.compare("resume_differing", bad)
    ctx.log(f"resumed from step {step}: {n} tokens decoded")

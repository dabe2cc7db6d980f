"""Smoke check of the hosted training and serving path on a TPU.

Drives the system's main path once through the entry points a user calls:
``CACSService`` over ``LocalBackend(1)`` hosts a ``TrainerApp`` and a
``ServeApp`` on full-width repro-100m with random weights from ``--seed``,
checkpoints them, swaps them out and restores them, and checks every
result against an uninterrupted run.

    python chip_smoke.py               # one chip: train, swap-out, serve
    python chip_smoke.py --four-chips  # four chips: reshard a saved image

Each phase prints one line with what it checked. Timings and memory on
those lines are smoke numbers, not benchmark results. The last line of
standard output is the JSON result, printed only when every phase passed.
The script exits non-zero, printing no result, when JAX finds no TPU.
Images go under ``--out-dir`` and are deleted when their jobs end.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                          # noqa: E402
import numpy as np                                  # noqa: E402

from repro.ckpt import LocalFSStore, restore, save_checkpoint  # noqa: E402
from repro.ckpt import compression                  # noqa: E402
from repro.ckpt.layout import leaf_items            # noqa: E402
from repro.ckpt.reader import load_manifest         # noqa: E402
from repro.clusters import LocalBackend             # noqa: E402
from repro.configs import get_config                # noqa: E402
from repro.configs.base import ArchConfig           # noqa: E402
from repro.core import (ASR, CACSService, CheckpointPolicy,  # noqa: E402
                        CoordState, clone)
from repro.kernels import qsnap                     # noqa: E402
from repro.obs.telemetry import registry            # noqa: E402
from repro.serve.engine import ServeApp             # noqa: E402
from repro.train.trainer import TrainerApp, _device_encodable  # noqa: E402

ARCH = "repro-100m"
WAIT_S = 600.0           # bound on any single wait; covers a cold compile

# Counters the control plane bumps instead of raising; any rise fails the run.
HEALTH_COUNTERS = ("appmgr.daemon_errors", "appmgr.op_errors",
                   "serve.decode_failures", "serve.stop_timeouts",
                   "trainer.step_failures", "ckpt.failed_saves")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def wait_for(pred: Callable[[], bool], what: str, *, app: Any = None,
             timeout_s: float = WAIT_S) -> None:
    """Poll ``pred`` until true; fail loudly on timeout or a dead app."""
    deadline = time.monotonic() + timeout_s
    while True:
        if app is not None and not app.healthy():
            raise SmokeFailure(f"{what}: app unhealthy "
                               f"({getattr(app, '_failure', None)!r})")
        if pred():
            return
        if time.monotonic() > deadline:
            raise SmokeFailure(f"{what}: not reached in {timeout_s:.0f}s")
        time.sleep(0.01)


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def say(phase: str, **fields: Any) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def host_tree(tree: Any) -> Dict[str, np.ndarray]:
    """name -> host ndarray for every array leaf (names as in manifests)."""
    return {name: np.asarray(jax.device_get(x))
            for name, x in leaf_items(tree) if hasattr(x, "shape")}


def trees_equal(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> bool:
    return a.keys() == b.keys() and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a)


class Health:
    """Error counters as deltas from construction, plus coordinator states."""

    def __init__(self):
        self._base = {n: registry().value(n, 0.0) for n in HEALTH_COUNTERS}

    def check(self, svc: CACSService, phase: str) -> None:
        rises = {n: registry().value(n, 0.0) - b
                 for n, b in self._base.items()}
        bad = {n: v for n, v in rises.items() if v}
        errored = [c.coord_id for c in svc.db.list()
                   if c.state == CoordState.ERROR]
        notes = {n: getattr(registry().get(n), "note", None) for n in bad}
        check(not bad and not errored,
              f"{phase}: error counters {bad} {notes}, ERROR coordinators "
              f"{errored}")


def make_service(out_dir: Path) -> CACSService:
    return CACSService({"local": LocalBackend(1)},
                       {"default": LocalFSStore(str(out_dir / "images"))})


# ---------------------------------------------------------------- phases

def train_phase(svc: CACSService, health: Health, cfg: ArchConfig, *,
                global_batch: int, seq_len: int, n_steps: int,
                restart_at: int, period_s: float, seed: int = 0) -> str:
    """Hosted training with periodic lossless saves, an explicit
    checkpoint mid-run and a restart from it; the losses and final state
    must equal an uninterrupted run bit for bit. Returns the coordinator
    id, still RUNNING with its job finished, for the swap-out phase."""
    def make_app() -> TrainerApp:
        return TrainerApp(cfg, global_batch=global_batch, seq_len=seq_len,
                          n_steps=n_steps, seed=seed)

    ref = make_app()
    ref.start(None, None)
    wait_for(ref.is_done, "reference run", app=ref)
    ref.stop()
    ref_losses = list(ref.losses)
    ref_state = host_tree(ref.checkpoint_state()["state"])
    compile_s = ref.step_times[0]
    steady = ref.step_times[2:] or ref.step_times
    del ref
    gc.collect()

    asr = ASR(name="smoke-train", n_vms=1, backend="local",
              app_factory=make_app,
              policy=CheckpointPolicy(period_s=period_s, codec="raw",
                                      keep_last=4, swap_codec="int8"))
    cid = svc.submit(asr)
    app = svc.wait_for_state(cid, CoordState.RUNNING, WAIT_S).app
    wait_for(lambda: app.current_step >= restart_at, "mid-run step",
             app=app)
    mid = svc.trigger_checkpoint(cid)
    wait_for(app.is_done, "first run", app=app)
    check(app.healthy(), "first run unhealthy")
    check(app.losses == ref_losses,
          "hosted run diverged from the standalone reference")

    svc.restart_from(cid, mid)
    svc.wait_for_state(cid, CoordState.RUNNING, WAIT_S)
    wait_for(app.is_done, "run after restart", app=app)
    after = app.losses[n_steps:]
    resumed_at = n_steps - len(after)
    check(app.restarts == 1 and 0 < len(after) < n_steps,
          f"restart resumed at step {resumed_at}")
    check(after == ref_losses[resumed_at:],
          f"losses after the restart from step {resumed_at} differ from "
          f"the uninterrupted run")
    check(trees_equal(host_tree(app.checkpoint_state()["state"]), ref_state),
          "final state after the restart differs from the uninterrupted run")
    svc.ckpt.wait(svc.db.get(cid), strict=False)
    images = svc.list_checkpoints(cid)
    health.check(svc, "train")
    say("train", steps=n_steps, restart_from_step=resumed_at,
        steps_after_restart=len(after), losses_bitexact=True,
        final_state_bitexact=True, images=len(images),
        loss_first=f"{ref_losses[0]:.4f}", loss_last=f"{ref_losses[-1]:.4f}",
        smoke_first_step_s=f"{compile_s:.2f}",
        smoke_median_step_s=f"{statistics.median(steady):.4f}",
        smoke_peak_bytes=peak_bytes())
    return cid


def swap_phase(svc: CACSService, health: Health, cid: str) -> str:
    """Suspend the finished training job to an int8 swap-out image made by
    the device encoder, then resume it. Every device-encoded QS01 payload
    must equal the host codec's bytes for the same leaf, and the restored
    state must be the host codec's decode, within its per-block bound.
    Returns the encoder implementation that ran."""
    coord = svc.db.get(cid)
    app = coord.app
    live = app.checkpoint_state()["state"]
    n_device = sum(1 for _, x in leaf_items(live) if _device_encodable(x)
                   and compression.is_float_dtype(x.dtype))
    pre = host_tree({"state": live})
    del live
    impl = qsnap._encode_impl()

    svc.apps.suspend(cid)
    step = svc.list_checkpoints(cid)[-1]
    info = svc.get_checkpoint(cid, step)
    check(info["codec"] == "int8" and "suspend" in info["metadata"],
          f"swap-out image {step} is not an int8 suspend image: {info}")
    store = svc.ckpt.store(coord.asr.policy.store)
    man = load_manifest(store, coord.ckpt_prefix, step)
    mismatches = compared = 0
    expected = {}
    max_err = 0.0
    for name, x in pre.items():
        if not compression.is_float_dtype(x.dtype):
            continue
        li = man.leaves[name]
        check(len(li.chunks) == 1, f"{name}: {len(li.chunks)} chunks")
        host = compression.encode(np.ascontiguousarray(x).tobytes(),
                                  x.dtype, "int8")
        compared += 1
        mismatches += store.get(li.chunks[0].key) != host
        # the codec's bound: half a quantization step of the leaf's block,
        # plus the f32 rounding of the dequantizing multiply
        xf = x.astype(np.float32).reshape(-1)
        codes, scales = compression.quantize_int8(xf)
        deq = compression.dequantize_int8(codes, scales, x.size)
        err = np.abs(deq - xf)
        bound = (np.repeat(scales, compression.BLOCK)[:x.size] * 0.5
                 + np.abs(xf) * 2.0 ** -22)
        check(bool(np.all(err <= bound)),
              f"{name}: host codec exceeds its per-block bound")
        max_err = max(max_err, float(err.max(initial=0.0)))
        expected[name] = deq.astype(x.dtype).reshape(x.shape)
    check(compared == n_device,
          f"{compared} float leaves in the image, {n_device} on device")
    check(mismatches == 0,
          f"{mismatches}/{compared} device-encoded QS01 payloads differ "
          f"from the host codec")

    svc.ckpt.wait(coord)                   # any save queued behind it
    check(svc.list_checkpoints(cid)[-1] == step,
          "an image newer than the swap-out image was committed")
    svc.apps.resume(cid)
    svc.wait_for_state(cid, CoordState.RUNNING, WAIT_S)
    check(app.restarts == 2 and app.healthy(), "resume from swap-out failed")
    got = host_tree({"state": app.checkpoint_state()["state"]})
    for name, want in expected.items():
        check(got[name].dtype == want.dtype
              and np.array_equal(got[name], want),
              f"{name}: restored leaf is not the int8 image's decode")
    for name in pre.keys() - expected.keys():
        check(np.array_equal(got[name], pre[name]),
              f"{name}: non-float leaf changed across the swap-out")
    svc.ckpt.wait(coord, strict=False)
    health.check(svc, "swap")
    say("swap", impl=impl, device_encoded_leaves=n_device,
        payloads_compared=compared, payload_mismatches=mismatches,
        restored_within_bound=True, max_abs_err=f"{max_err:.3e}",
        smoke_peak_bytes=peak_bytes())
    return impl


def serve_phase(svc: CACSService, health: Health, cfg: ArchConfig, *,
                batch: int, prompt_len: int, n_tokens: int,
                suspend_at: int, token_delay_s: float,
                seed: int = 0) -> None:
    """Hosted serving suspended mid-decode, then resumed in a fresh app
    (a clone of the swap-out image); the tokens must equal an unsuspended
    run's."""
    def make_app(delay: float = token_delay_s) -> ServeApp:
        return ServeApp(cfg, batch=batch, prompt_len=prompt_len,
                        n_tokens=n_tokens, cache_len=prompt_len + n_tokens,
                        seed=seed, token_delay_s=delay)

    ref = make_app(0.0)
    ref.start(None, None)
    wait_for(ref.is_done, "reference decode", app=ref)
    check(not ref.stop(), "reference decode thread leaked")
    ref_tokens = ref.checkpoint_state()["tokens_out"]
    del ref
    gc.collect()

    asr = ASR(name="smoke-serve", n_vms=1, backend="local",
              app_factory=make_app, policy=CheckpointPolicy(period_s=0))
    cid = svc.submit(asr)
    app = svc.wait_for_state(cid, CoordState.RUNNING, WAIT_S).app
    wait_for(lambda: app.generated >= suspend_at, "decode to suspend point",
             app=app)
    svc.apps.suspend(cid)
    coord = svc.db.get(cid)
    step = svc.list_checkpoints(cid)[-1]
    pinned = int(svc.ckpt.load(coord, step)["generated"])
    check(0 < pinned < n_tokens, f"suspended at token {pinned}, not mid-decode")

    res = clone(svc, cid, svc, backend="local", step=step,
                fresh_checkpoint=False)
    fresh = svc.db.get(res.dst_id).app
    check(fresh is not app and fresh.restarts == 1,
          "resume did not build a fresh app")
    wait_for(fresh.is_done, "decode after resume", app=fresh)
    tokens = fresh.checkpoint_state()["tokens_out"]
    check(tokens.shape == ref_tokens.shape
          and np.array_equal(tokens, ref_tokens),
          "tokens after suspend and resume differ from the unsuspended run")
    health.check(svc, "serve")
    say("serve", batch=batch, prompt=prompt_len, tokens=n_tokens,
        suspended_at_token=pinned, tokens_identical=True,
        smoke_peak_bytes=peak_bytes())


def reshard_phase(cfg: ArchConfig, devices, out_dir: Path, *,
                  global_batch: int, seq_len: int, seed: int = 0,
                  loss_atol: float = 2e-2) -> None:
    """Train state sharded over data=4 x model=1, saved, restored under
    data=2 x model=2: the restored state must be the saved one bit for bit
    and sit shard by shard on every device, and two more steps on each
    layout must give losses within ``loss_atol`` (the layouts reduce in
    different orders, so bf16 losses agree only to rounding)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.data.pipeline import TokenPipeline
    from repro.models import build_model
    from repro.sharding.specs import make_axes, param_specs
    from repro.train import AdamWConfig, init_state, make_train_step
    from repro.train.trainer import state_dims

    model = build_model(cfg)
    opt = AdamWConfig(warmup_steps=5, total_steps=8)
    sds = jax.eval_shape(lambda: init_state(model, jax.random.PRNGKey(seed)))

    def layout(shape):
        mesh = Mesh(np.asarray(devices[:4]).reshape(shape),
                    ("data", "model"))
        axes = make_axes(mesh, use_fsdp=True)
        named = lambda spec: NamedSharding(mesh, spec)   # noqa: E731
        is_spec = lambda x: isinstance(x, P)             # noqa: E731
        st = jax.tree.map(named, param_specs(state_dims(model), sds, axes),
                          is_leaf=is_spec)
        bt = jax.tree.map(named, param_specs(
            model.batch_dims(), model.batch_struct(global_batch, seq_len),
            axes), is_leaf=is_spec)
        step = jax.jit(make_train_step(model, opt, axes=axes),
                       out_shardings=(st, None))
        return mesh, st, bt, step

    def run(state, pipe, mesh, bt, step, n):
        losses = []
        with jax.set_mesh(mesh):          # activation constraints name axes
            for _ in range(n):
                state, m = step(state, pipe.next(bt))
                losses.append(float(m["loss"]))
        return state, losses

    mesh_a, st_a, bt_a, step_a = layout((4, 1))
    state_a = jax.jit(lambda: init_state(model, jax.random.PRNGKey(seed)),
                      out_shardings=st_a)()
    pipe_a = TokenPipeline(cfg, global_batch, seq_len, seed=seed)
    state_a, _ = run(state_a, pipe_a, mesh_a, bt_a, step_a, 2)

    store = LocalFSStore(str(out_dir / "images"))
    save_checkpoint(store, "reshard", 2,
                    {"state": state_a, "data": pipe_a.state_dict()})
    saved = host_tree(state_a)

    mesh_b, st_b, bt_b, step_b = layout((2, 2))
    snap, _ = restore(store, "reshard", 2,
                      shardings={"state": st_b, "data": None})
    state_b = snap["state"]
    check(trees_equal(host_tree(state_b), saved),
          "state restored under data=2 x model=2 differs from the saved one")
    per_device: Dict[Any, int] = {d: 0 for d in mesh_b.devices.flat}
    sharded_leaves = 0
    for (name, x), want in zip(leaf_items(state_b),
                               jax.tree_util.tree_leaves(st_b)):
        check(x.sharding.is_equivalent_to(want, x.ndim),
              f"{name}: restored with {x.sharding}, wanted {want}")
        shards = x.addressable_shards
        check({s.device for s in shards} == set(per_device),
              f"{name}: shards on {[s.device for s in shards]}")
        for s in shards:
            check(s.data.shape == want.shard_shape(x.shape)
                  and s.data.devices() == {s.device},
                  f"{name}: shard {s.index} misplaced")
            per_device[s.device] += s.data.nbytes
        sharded_leaves += not want.is_fully_replicated
    check(sharded_leaves > 0, "no leaf is sharded under data=2 x model=2")

    pipe_b = TokenPipeline(cfg, global_batch, seq_len, seed=seed)
    pipe_b.load_state_dict(snap["data"])
    _, losses_a = run(state_a, pipe_a, mesh_a, bt_a, step_a, 2)
    _, losses_b = run(state_b, pipe_b, mesh_b, bt_b, step_b, 2)
    diff = max(abs(a - b) for a, b in zip(losses_a, losses_b))
    check(diff <= loss_atol,
          f"losses after the reshard {losses_b} vs {losses_a}")
    store.delete_prefix("reshard")
    say("reshard", save_mesh="data=4xmodel=1", restore_mesh="data=2xmodel=2",
        restored_bitexact=True, sharded_leaves=sharded_leaves,
        bytes_per_device=sorted(per_device.values()),
        losses_saved_layout=[f"{v:.5f}" for v in losses_a],
        losses_restored_layout=[f"{v:.5f}" for v in losses_b],
        max_loss_diff=f"{diff:.2e}", loss_atol=loss_atol)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip resharding phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=str(ROOT / "smoke_out"))
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    out_dir = Path(args.out_dir)
    shutil.rmtree(out_dir / "images", ignore_errors=True)
    cfg = get_config(ARCH)                 # full width, random weights
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), arch=ARCH, params=cfg.param_count(),
        compile_cache=cache_dir)

    if args.four_chips:
        reshard_phase(cfg, devices, out_dir, global_batch=8, seq_len=256,
                      seed=args.seed)
    else:
        svc = make_service(out_dir)
        health = Health()
        try:
            cid = train_phase(svc, health, cfg, global_batch=8, seq_len=256,
                              n_steps=30, restart_at=18, period_s=5.0,
                              seed=args.seed)
            impl = swap_phase(svc, health, cid)
            check(impl == "pallas",
                  f"swap-out encoded with the {impl!r} impl on a TPU")
            svc.delete_coordinator(cid)
            serve_phase(svc, health, cfg, batch=2, prompt_len=32,
                        n_tokens=64, suspend_at=8, token_delay_s=0.05,
                        seed=args.seed)
            health.check(svc, "health")
        finally:
            svc.shutdown()
        say("health", counters="+0", error_coordinators=0)
    cached = sum(1 for _ in Path(cache_dir).glob("*")) \
        if Path(cache_dir).is_dir() else 0
    say("cache", dir=cache_dir, entries=cached)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

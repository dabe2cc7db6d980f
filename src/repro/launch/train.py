"""Training launcher.

Two modes:
  * ``--managed``  — submit the job to a CACS service instance (checkpoint
    policy, health monitoring, failure recovery all owned by the service —
    the paper's deployment model).
  * raw           — plain loop with an AsyncCheckpointer (for debugging).

On real hardware this process runs once per host; on this CPU container it
drives a single-device run (the multi-pod path is exercised by dryrun.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-period", type=float, default=10.0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--codec", default="raw",
                    choices=["raw", "zlib", "int8", "int8+zlib"])
    ap.add_argument("--managed", action="store_true",
                    help="run under a CACS service instance")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke-test config")
    args = ap.parse_args()

    from repro.configs import get_config, reduced
    from repro.launch.cache import enable_compile_cache
    from repro.train.trainer import TrainerApp

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.managed:
        from repro.ckpt import LocalFSStore
        from repro.clusters import LocalBackend
        from repro.core import ASR, CACSService, CheckpointPolicy, CoordState
        svc = CACSService({"local": LocalBackend(n_hosts=1)},
                          {"default": LocalFSStore(args.ckpt_dir)})
        asr = ASR(name=f"train-{cfg.name}", n_vms=1, backend="local",
                  app_factory=lambda: TrainerApp(
                      cfg, global_batch=args.batch, seq_len=args.seq,
                      n_steps=args.steps),
                  policy=CheckpointPolicy(period_s=args.ckpt_period,
                                          codec=args.codec, keep_last=3))
        cid = svc.submit(asr)
        svc.wait_for_state(cid, CoordState.RUNNING, timeout=600)
        print(f"coordinator {cid} RUNNING")
        coord = svc.db.get(cid)
        while not coord.app.is_done():
            time.sleep(2.0)
            print(f"step={coord.app.current_step} loss={coord.app.last_loss:.4f} "
                  f"ckpts={svc.list_checkpoints(cid)}")
        svc.shutdown()
        return

    # raw loop
    import jax
    from repro.ckpt import AsyncCheckpointer, LocalFSStore, latest_step, restore
    from repro.data.pipeline import TokenPipeline
    from repro.models import build_model
    from repro.train import AdamWConfig, init_state, make_train_step

    model = build_model(cfg)
    opt = AdamWConfig(total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, opt))
    store = LocalFSStore(args.ckpt_dir)
    pipeline = TokenPipeline(cfg, args.batch, args.seq)
    ck = AsyncCheckpointer(store, f"raw/{cfg.name}", codec=args.codec)

    if args.resume and latest_step(store, f"raw/{cfg.name}") is not None:
        snap, man = restore(store, f"raw/{cfg.name}")
        state = snap["state"]
        pipeline.load_state_dict(snap["data"])
        print(f"resumed from step {man.step}")
    else:
        state = init_state(model, jax.random.PRNGKey(0))

    last_ckpt = time.monotonic()
    while int(state["step"]) < args.steps:
        batch = pipeline.next()
        batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
        state, metrics = step_fn(state, batch)
        s = int(state["step"])
        if s % 10 == 0:
            print(f"step={s} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
        if time.monotonic() - last_ckpt > args.ckpt_period:
            ck.save(s, {"state": state, "data": pipeline.state_dict()})
            last_ckpt = time.monotonic()
    ck.save(int(state["step"]),
            {"state": state, "data": pipeline.state_dict()})
    ck.close()
    print("done")


if __name__ == "__main__":
    main()

"""Serving launcher: batched greedy generation, optionally CACS-managed
(a suspended serving job resumes mid-generation from its KV-cache image).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--managed", action="store_true")
    args = ap.parse_args()

    from repro.configs import get_config, reduced
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.managed:
        from repro.ckpt import InMemoryStore
        from repro.clusters import LocalBackend
        from repro.core import ASR, CACSService, CheckpointPolicy, CoordState
        from repro.serve.engine import ServeApp
        svc = CACSService({"local": LocalBackend(1)},
                          {"default": InMemoryStore()})
        asr = ASR(name=f"serve-{cfg.name}", n_vms=1, backend="local",
                  app_factory=lambda: ServeApp(
                      cfg, batch=args.batch, prompt_len=args.prompt_len,
                      n_tokens=args.tokens,
                      cache_len=args.prompt_len + args.tokens),
                  policy=CheckpointPolicy(period_s=1.0, keep_last=2))
        cid = svc.submit(asr)
        svc.wait_for_state(cid, CoordState.RUNNING, timeout=600)
        coord = svc.db.get(cid)
        while not coord.app.is_done():
            time.sleep(1.0)
            print(f"generated {coord.app.generated}/{args.tokens}")
        print("tokens:", coord.app.checkpoint_state()["tokens_out"][:, :16])
        svc.shutdown()
        return

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model
    from repro.serve.engine import Engine

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params,
                    cache_len=args.prompt_len + args.tokens)
    rng = np.random.Generator(np.random.PCG64(0))
    prompt = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.monotonic()
    out = engine.generate({"tokens": jnp.asarray(prompt)}, args.tokens)
    dt = time.monotonic() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s)")
    print(np.asarray(out[:, :16]))


if __name__ == "__main__":
    main()

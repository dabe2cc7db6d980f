"""Readings of the program and of the control on the same seeds, in one
process. The control is the plain reference put in the program's place,
computed one precision below the configuration's (float8 e4m3 matmuls for
bf16). Each limit of a cell sits between the program's sound readings and
the control's.

    python -m chipbench.control --workload <cell> --seeds 1,2,3 \
        [--seconds 10]

For each seed the cell runs as a run would (with a window of
``--seconds``) and its compared numbers are kept; then the control's:
a training cell's control follows the same first steps from the seed, a
serving cell's reads, over the same prompts and served tokens, the gap of
the token the control puts first. The control's readings are compared
with the cell's limits the way a run's are, and its ``correct`` has to
come out false. A training cell also reads, under ``half_batch``, the
gaps of the full-precision reference on half of each batch (a fault a
training step can have). One JSON line per seed. Needs a TPU, as a run
does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def control_run(ctx, bench, device) -> dict:
    """One seed's run of the cell, then the control's readings on the same
    seed, compared with the cell's limits the way a run's are (the
    control's ``correct`` has to come out false)."""
    from chipbench import configs
    from chipbench.harness import Ctx, control_train_steps
    from chipbench.run import execute
    res = execute(ctx, bench, device)
    out = {"seed": ctx.seed, "workload": ctx.args.workload,
           "program": res["checks"], "metrics": res["metrics"],
           "failed": res["failed"]}
    t = time.perf_counter()
    if ctx.workload["driver"] in ("train", "swap"):
        gaps = control_train_steps(ctx)
    else:
        serve = configs.driver(ctx.workload["driver"])
        gaps = {"token_logit_gap": serve.max_token_gap(
            ctx, ctx.record["served"], lowp=True)}
    ctl = Ctx(ctx.args, t, workload=ctx.workload, config=ctx.config)
    for name, v in gaps.items():
        ctl.compare(name, v)
    out.update(correct=ctl.correct(),
               checks={n: {"value": v, "limit": lim}
                       for n, v, lim in ctl.compared},
               seconds=time.perf_counter() - t)
    if ctx.workload["driver"] in ("train", "swap"):
        out["half_batch"] = control_train_steps(
            ctx, rows=ctx.workload["batch"] // 2)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench.control: needs a TPU", file=sys.stderr)
        return 3
    from chipbench import configs, program  # noqa: F401
    from chipbench.harness import Ctx
    from chipbench.run import enable_compile_cache
    enable_compile_cache()
    bench = configs.benchmark()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        out = control_run(Ctx(ns, time.perf_counter()), bench, device)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the chip benchmark and print its result line.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on and needs the TPU chips the cell
asks for: with no TPU, or too few, it exits non-zero and prints no result.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each compared number beside its
limit); the same comparisons are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from chipbench import configs  # noqa: E402


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_entry(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(configs.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def execute(ctx, bench: Dict[str, Any], device: Dict[str, Any]
            ) -> Dict[str, Any]:
    """Drive the cell, then reduce what it found to the result line."""
    from chipbench.peaks import peaks_for
    wl = ctx.workload
    ctx.record["peaks"] = peaks_for(device["kind"])
    ctx.record["config"], ctx.record["workload"] = ctx.config, wl
    configs.driver(wl["driver"]).run(ctx)

    name = ctx.args.workload
    for m in configs.cell_metrics(bench, name, "per_layer"):
        ctx.log(f"{m['name']} = {configs.reader(m['name']).read(ctx.record)}")
    metrics: Dict[str, Dict[str, Any]] = {}
    if ctx.trace:
        for m in configs.cell_metrics(bench, name, "per_layer"):
            v = configs.reader(m["name"]).read(ctx.record)
            if v is None:
                raise RuntimeError(f"{m['name']}: nothing to read in {name}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(ctx.e2e, setup_s=ctx.setup_s)
        for m in configs.cell_metrics(bench, name, "end_to_end"):
            if values.get(m["name"]) is None:
                raise RuntimeError(f"{m['name']}: not measured in {name}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=ctx.memory_peak)
    out: Dict[str, Any] = {"correct": ctx.correct(),
                           "attempted": ctx.attempted, "failed": ctx.failed,
                           "metrics": metrics, "device": dev}
    if ctx.trace:
        t = ctx.record["trace"]
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["top_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in ctx.compared}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    bench = configs.benchmark()
    chips = cell_entry(bench, args.workload)["chips"]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: cell {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 3
    enable_compile_cache()
    from chipbench import program  # noqa: F401  (puts src on the path)
    from chipbench.harness import Ctx
    ctx = Ctx(args, T_START)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    out = execute(ctx, bench, device)
    for n, c in out["checks"].items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

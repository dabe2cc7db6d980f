import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import: jax locks the device
# count at first init, and the production meshes need 512 placeholder
# CPU devices (2 pods x 16 x 16) — pinned to the CPU so this process and
# the per-cell children it starts never claim an accelerator. Everything
# else imports below.

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, \
    shape_applicable  # noqa: E402


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             remat: bool = True, fsdp=None, seq_shard=None,
             tag: str = "", full_compile: bool = True) -> dict:
    from repro.launch.lowering import lower_and_analyze
    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell_args = dict(arch=arch, shape=shape, remat=remat, fsdp=fsdp,
                     seq_shard=seq_shard)
    result = lower_and_analyze(cell_args, mesh, full_compile=full_compile)
    if tag:
        result["tag"] = tag
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        mesh_tag = "2x16x16" if multi_pod else "16x16"
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}_{shape}_{mesh_tag}{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def run_all(out_dir: str, multi_pod_list, jobs_filter=None) -> int:
    """Drive every (arch x shape x mesh) cell in a subprocess each (compile
    state isolation; a crashing cell doesn't take down the sweep)."""
    failures = 0
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name in SHAPES:
            ok, why = shape_applicable(cfg, SHAPES[shape_name])
            if not ok:
                print(f"SKIP  {arch:28s} {shape_name:12s} {why}")
                continue
            for mp in multi_pod_list:
                mesh_tag = "2x16x16" if mp else "16x16"
                if jobs_filter and (arch, shape_name, mesh_tag) not in jobs_filter:
                    continue
                path = os.path.join(
                    out_dir, f"{arch}_{shape_name}_{mesh_tag}.json")
                if os.path.exists(path):
                    print(f"HAVE  {arch:28s} {shape_name:12s} {mesh_tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", out_dir]
                if mp:
                    cmd.append("--multi-pod")
                t0 = time.monotonic()
                r = subprocess.run(cmd, capture_output=True, text=True)
                dt = time.monotonic() - t0
                if r.returncode != 0:
                    failures += 1
                    print(f"FAIL  {arch:28s} {shape_name:12s} {mesh_tag} "
                          f"({dt:.0f}s)\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
                else:
                    print(f"OK    {arch:28s} {shape_name:12s} {mesh_tag} "
                          f"({dt:.0f}s)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on both meshes, "
                         "one subprocess per cell")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default="",
                    help="e.g. save_moe (selective remat)")
    ap.add_argument("--fsdp", choices=["on", "off"])
    ap.add_argument("--seq-shard", choices=["on", "off"])
    ap.add_argument("--tag", default="", help="variant tag for perf runs")
    ap.add_argument("--quick", action="store_true",
                    help="skip the full-depth compile (perf iterations)")
    args = ap.parse_args()

    if args.all:
        failures = run_all(args.out, multi_pod_list=[False, True])
        sys.exit(1 if failures else 0)

    fsdp = None if args.fsdp is None else args.fsdp == "on"
    seq_shard = None if args.seq_shard is None else args.seq_shard == "on"
    remat = args.remat_policy or (not args.no_remat)
    result = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                      remat=remat, fsdp=fsdp,
                      seq_shard=seq_shard, tag=args.tag,
                      full_compile=not args.quick)
    # the assignment's required proofs:
    head = {k: result.get(k) for k in
            ("arch", "shape", "mesh", "lower_s", "compile_s")}
    print(json.dumps(head))
    if "memory_analysis" in result:
        print("memory_analysis:", json.dumps(result["memory_analysis"]))
    print("cost_analysis: flops/device=%.3e bytes/device=%.3e"
          % (result["flops_per_device"], result["bytes_per_device"]))
    print("collectives:", json.dumps(result["collectives"]))
    print("roofline:", json.dumps(result["roofline"]))


if __name__ == "__main__":
    main()

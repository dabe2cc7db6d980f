import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# Perf-iteration probe: compile a depth-2 unrolled cell on 512 placeholder
# CPU devices and print the top collectives + cost numbers — the dry-run
# equivalent of a profiler trace.

import argparse      # noqa: E402
import json          # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--fsdp", choices=["on", "off"])
    ap.add_argument("--seq-shard", choices=["on", "off"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--save-hlo", default="")
    args = ap.parse_args()

    from repro.launch import analysis
    from repro.launch.lowering import _compile_cell, build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()
    fsdp = None if args.fsdp is None else args.fsdp == "on"
    seq_shard = None if args.seq_shard is None else args.seq_shard == "on"
    cell = build_cell(args.arch, args.shape, mesh, depth_groups=args.depth,
                      remat=not args.no_remat, fsdp=fsdp,
                      seq_shard=seq_shard)
    with mesh:
        lowered = cell.jitted.lower(*cell.args)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    if args.save_hlo:
        with open(args.save_hlo, "w") as f:
            f.write(hlo)
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(hlo)
    print(json.dumps({
        "flops": cost.get("flops"),
        "bytes": cost.get("bytes accessed"),
        "collectives": {k: v for k, v in coll.items() if v},
    }, indent=1))
    print("\ntop collectives (bytes, op, op_name):")
    for nbytes, op, meta in analysis.top_collectives(hlo, args.top):
        print(f"  {nbytes/1e6:10.1f}MB  {op:20s} {meta}")


if __name__ == "__main__":
    main()

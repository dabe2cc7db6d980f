"""Memory rehearsal without the chip: compile each cell's programs for a
described TPU v5e at the cell's shapes and print ``memory_analysis``.

    JAX_PLATFORMS=cpu python -m chipbench.rehearse [--cells train-save,...]

Compiles the train step (as ``TrainerApp`` jits it: no donation), the
serving prefill and decode (as ``Engine`` jits them: decode donates the
cache) and the int8 ``qsnap`` encode of the largest train-state leaf.
Prints one JSON line per program.
"""
from __future__ import annotations

import argparse
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import configs, program  # noqa: E402


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def _reference_train(out, cfg, wl, on_chip, one) -> None:
    import jax
    import jax.numpy as jnp
    ref = configs.reference(cfg["reference"])
    params = on_chip(jax.eval_shape(lambda: ref.make_init(cfg)(
        jax.random.PRNGKey(0))))
    f32 = on_chip(jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32), params))
    toks = jax.ShapeDtypeStruct((wl["batch"], wl["seq_len"]), jnp.int32,
                                sharding=one)
    cnt = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    for lowp in (False, True):
        def step(p, m, v, c, t, y):
            loss, g = ref.loss_and_grad(cfg, p, t, y, lowp)
            return ref.adamw(wl["optimizer"], p, g, m, v, c), loss
        c = jax.jit(step).lower(params, f32, f32, cnt, toks, toks).compile()
        print(json.dumps({**out, "program": f"reference_step_lowp{lowp:d}",
                          **_mem(c)}), flush=True)


def _reference_serve(out, cfg, wl, on_chip) -> None:
    import jax
    import jax.numpy as jnp
    ref = configs.reference(cfg["reference"])
    params = on_chip(jax.eval_shape(lambda: ref.make_init(cfg)(
        jax.random.PRNGKey(0))))
    seq = on_chip(jax.ShapeDtypeStruct((wl["cache_len"],), jnp.int32))
    for lowp in (False, True):
        c = jax.jit(lambda p, t: ref.hidden(cfg, p, t, lowp)).lower(
            params, seq).compile()
        print(json.dumps({**out, "program": f"reference_hidden_lowp{lowp:d}",
                          "positions": wl["cache_len"], **_mem(c)}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="train-save,serve-pin")
    ap.add_argument("--reference", action="store_true",
                    help="also compile the plain reference's programs")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels.qsnap import qsnap_quantize
    from repro.models import build_model
    from repro.train import AdamWConfig, init_state, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    for cell in args.cells.split(","):
        wl = configs.workload(cell)
        cfg = configs.config(wl["config"])
        model = build_model(program.arch(cfg))
        out = {"cell": cell}
        if wl["driver"] in ("train", "swap"):
            state = on_chip(jax.eval_shape(
                lambda: init_state(model, jax.random.PRNGKey(0))))
            batch = on_chip(model.batch_struct(wl["batch"], wl["seq_len"]))
            step = jax.jit(make_train_step(model, AdamWConfig()))
            c = step.lower(state, batch).compile()
            print(json.dumps({**out, "program": "train_step", **_mem(c)}),
                  flush=True)
            big = max(jax.tree_util.tree_leaves(state), key=lambda s: s.size)
            flat = jax.ShapeDtypeStruct((big.size,), jnp.float32,
                                        sharding=one)
            c = jax.jit(qsnap_quantize).lower(flat).compile()
            print(json.dumps({**out, "program": "qsnap_encode",
                              "elements": big.size, **_mem(c)}), flush=True)
            if args.reference:
                _reference_train(out, cfg, wl, on_chip, one)
        else:
            params = on_chip(model.abstract_params())
            toks = on_chip({"tokens": jax.ShapeDtypeStruct(
                (wl["batch"], wl["prompt_len"]), jnp.int32)})
            pre = jax.jit(lambda p, b: model.prefill(
                p, b, cache_len=wl["cache_len"]))
            c = pre.lower(params, toks).compile()
            print(json.dumps({**out, "program": "prefill", **_mem(c)}),
                  flush=True)
            cache = on_chip(model.abstract_cache(wl["batch"],
                                                 wl["cache_len"]))
            tok = on_chip(jax.ShapeDtypeStruct((wl["batch"], 1), jnp.int32))
            pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
            dec = jax.jit(model.decode_step, donate_argnums=(1,))
            c = dec.lower(params, cache, tok, pos).compile()
            print(json.dumps({**out, "program": "decode", **_mem(c)}),
                  flush=True)
            if args.reference:
                _reference_serve(out, cfg, wl, on_chip)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Cross-mesh checkpoint resharding (the migration core) — runs in
subprocesses with 8 forced host devices so the main test process keeps its
single real CPU device."""
import pytest

from tests.conftest import run_subprocess


def test_save_reshard_restore_roundtrip():
    run_subprocess("""
    import itertools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt import InMemoryStore, save_checkpoint, restore
    from repro.launch.mesh import make_test_mesh

    meshes = {
        "4x2": make_test_mesh((4, 2), ("data", "model")),
        "2x4": make_test_mesh((2, 4), ("data", "model")),
        "8x1": make_test_mesh((8, 1), ("data", "model")),
        "2x2": make_test_mesh((2, 2), ("data", "model")),
    }
    specs = [P("data", "model"), P("model", "data"), P(None, "model"),
             P("data", None), P()]
    x = jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32)
    ref = np.asarray(x)
    cases = 0
    for (mn1, m1), s1 in itertools.product(meshes.items(), specs):
        store = InMemoryStore()
        xs = jax.device_put(x, NamedSharding(m1, s1))
        save_checkpoint(store, "p", 1, {"w": xs})
        for (mn2, m2), s2 in itertools.product(meshes.items(), specs):
            out, _ = restore(store, "p",
                             shardings={"w": NamedSharding(m2, s2)})
            assert out["w"].sharding.spec == s2
            np.testing.assert_array_equal(np.asarray(out["w"]), ref), \\
                (mn1, s1, mn2, s2)
            cases += 1
    print("CASES", cases)
    """, devices=8)


def test_trainer_state_elastic_restore():
    """Save a sharded train state on a 4x2 mesh, restore on 2x4 and verify
    a further train step matches the unsharded reference run."""
    run_subprocess("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt import InMemoryStore, save_checkpoint, restore
    from repro.configs import get_config, reduced
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import make_test_mesh
    from repro.models import build_model
    from repro.sharding.specs import make_axes, param_specs
    from repro.train import AdamWConfig, init_state, make_train_step
    from repro.train.trainer import state_dims

    cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b")),
                              dtype="float32")
    model = build_model(cfg)
    opt = AdamWConfig(warmup_steps=1, total_steps=8)
    step = jax.jit(make_train_step(model, opt))
    pipe = TokenPipeline(cfg, 4, 32, seed=0)

    # reference: 4 steps single-device
    state = init_state(model, jax.random.PRNGKey(0))
    for _ in range(4):
        b = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        state, m = step(state, b)
    ref_loss = float(m["loss"])

    # sharded run: 2 steps on 4x2, checkpoint, restore on 2x4, 2 more steps
    mesh1 = make_test_mesh((4, 2), ("data", "model"))
    axes1 = make_axes(mesh1)
    sds = jax.eval_shape(lambda: init_state(model, jax.random.PRNGKey(0)))
    specs1 = param_specs(state_dims(model), sds, axes1)
    sh1 = jax.tree.map(lambda s: NamedSharding(mesh1, s), specs1,
                       is_leaf=lambda x: isinstance(x, P))
    state2 = jax.device_put(init_state(model, jax.random.PRNGKey(0)), sh1)
    pipe2 = TokenPipeline(cfg, 4, 32, seed=0)
    with mesh1:
        for _ in range(2):
            b = {k: jnp.asarray(v) for k, v in pipe2.next().items()}
            state2, _ = step(state2, b)
    store = InMemoryStore()
    save_checkpoint(store, "t", 2,
                    {"state": state2, "data": pipe2.state_dict()})

    mesh2 = make_test_mesh((2, 4), ("data", "model"))
    axes2 = make_axes(mesh2)
    specs2 = param_specs(state_dims(model), sds, axes2)
    sh2 = jax.tree.map(lambda s: NamedSharding(mesh2, s), specs2,
                       is_leaf=lambda x: isinstance(x, P))
    snap, _ = restore(store, "t", shardings={"state": sh2, "data": None})
    state3 = snap["state"]
    pipe3 = TokenPipeline(cfg, 4, 32, seed=0)
    pipe3.load_state_dict(snap["data"])
    with mesh2:
        for _ in range(2):
            b = {k: jnp.asarray(v) for k, v in pipe3.next().items()}
            state3, m3 = step(state3, b)
    got = float(m3["loss"])
    print("ref", ref_loss, "elastic", got)
    assert abs(got - ref_loss) < 2e-5, (got, ref_loss)
    """, devices=8, timeout=560)


def test_small_mesh_sharded_train_step_runs():
    """Execute a sharded train step on a 2x4 mesh (the layout a resumed
    job resharded to data x model runs under) and check loss finiteness +
    param sharding layout."""
    run_subprocess("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, reduced
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import make_test_mesh
    from repro.models import build_model
    from repro.sharding.specs import make_axes, param_specs
    from repro.train import AdamWConfig, init_state, make_train_step
    from repro.train.trainer import state_dims

    cfg = dataclasses.replace(reduced(get_config("llama4-scout-17b-a16e")),
                              dtype="float32")
    model = build_model(cfg)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    axes = make_axes(mesh, use_fsdp=True)
    step = jax.jit(make_train_step(model, AdamWConfig(), axes=axes))
    sds = jax.eval_shape(lambda: init_state(model, jax.random.PRNGKey(0)))
    specs = param_specs(state_dims(model), sds, axes)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(init_state(model, jax.random.PRNGKey(0)), sh)
    pipe = TokenPipeline(cfg, 4, 32, seed=0)
    with mesh:
        for _ in range(2):
            b = {k: jnp.asarray(v) for k, v in pipe.next().items()}
            state, m = step(state, b)
    assert np.isfinite(float(m["loss"]))
    # MoE expert weights must actually be expert-sharded over 'model'
    we = state["params"]["stack"]["l0_moe"]["we_u"]
    assert "model" in str(we.sharding.spec), we.sharding
    print("loss", float(m["loss"]))
    """, devices=8, timeout=560)

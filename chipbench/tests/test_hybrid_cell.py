"""CPU tests of the hybrid serving cell: the Jamba configuration file and
its counts, and tiny runs of ``hybrid-serve-pin`` through ``execute``
with a sound program, its control and planted faults. No topology is
described and no TPU is touched.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import configs, hybrid_counts, program  # noqa: E402,F401

CELL = "hybrid-serve-pin"
CATALOG = ("attn_layer_offset", "attn_layer_period", "hidden_size",
           "intermediate_size", "mamba_d_conv", "mamba_d_state",
           "mamba_dt_rank", "mamba_expand", "num_attention_heads",
           "num_hidden_layers", "num_key_value_heads", "rms_norm_eps",
           "tie_word_embeddings", "vocab_size")

# one period of 14 layers at small widths; d_state stays 16
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=1, vocab_size=300, mamba_dt_rank=4)
# Set by the cells' rule between the tiny program's gaps on the CPU (three
# seeds: at most 0.10) and the tiny control's (at least 0.54); the cell's
# limit, set at the published widths, does not carry down to them.
TINY_LIMITS = {"token_logit_gap": 0.3}
TINY_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def test_the_configuration_runs_at_the_published_sizes():
    cfg = configs.config("jamba2-3b-serve")
    bench = configs.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == []
    from repro.configs import get_config
    a = configs.driver("serve_hybrid").arch(cfg)
    listed = get_config("jamba2-3b")
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "norm_eps", "tie_embeddings",
              "attn_every", "attn_offset", "use_rope", "ssm"):
        assert getattr(a, f) == getattr(listed, f), f
    assert [i for i in range(a.n_layers) if a.is_attn_layer(i)] == [7, 21]
    for k in CATALOG:
        assert k in cfg


def test_hybrid_counts_match_the_hand_worked_figures():
    cfg = configs.config("jamba2-3b-serve")
    assert hybrid_counts.param_count(cfg) == 3_029_337_472   # 6.06 GB bf16
    assert hybrid_counts.kv_bytes_per_token(cfg) == 1024
    # 26 x (h 16 x 5120 f32 + conv 3 x 5120 bf16) = 9.32 MB a sequence
    assert hybrid_counts.state_bytes_per_row(cfg) == 9_318_400
    cost = hybrid_counts.decode_step_cost(cfg, 32, 0)
    assert cost["bytes"] == 2 * 3_029_337_472 + 2 * 32 * 9_318_400
    # 32 prompts of 8192 tokens: about 1.5 PFLOP
    assert round(hybrid_counts.prefill_flops(cfg, 32, 8192) / 1e15, 2) \
        == 1.52


def tiny_ctx(seed=2147483711, seconds=2.0, **wl_changes):
    from chipbench.harness import Ctx
    wl = dict(configs.workload(CELL))
    cfg = dict(configs.config(wl["config"]), **TINY)
    wl.update(dict(batch=3, prompt_len=16, cache_len=4096, n_tokens=4000),
              **wl_changes)
    # one snapshot early in the window, as the cell has: the resume needs
    # the live run to go on for resume_tokens after it
    wl["saves"] = dict(wl["saves"], first_s=0.2)
    wl["limits"] = {k: TINY_LIMITS.get(k, v)
                    for k, v in wl["limits"].items()}
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0)
    return Ctx(args, time.perf_counter(), workload=wl, config=cfg)


def tiny_run(**kw):
    import jax
    from chipbench.run import execute
    jax.config.update("jax_enable_compilation_cache", False)
    ctx = tiny_ctx(**kw)
    return ctx, execute(ctx, configs.benchmark(), TINY_DEVICE)


def failing(out):
    return {n for n, c in out["checks"].items() if c["value"] > c["limit"]}


def test_a_sound_tiny_run_is_correct():
    ctx, out = tiny_run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["checks"]) == {"token_logit_gap", "image_leaves_differing",
                                  "resume_differing"}
    assert set(out["metrics"]) == {"token_gap_p95_ms", "decode_tokens_per_s",
                                   "setup_s"}
    rec = ctx.record
    assert rec["prefill"]["rows"] == 3 and rec["prefill"]["tokens"] == 48
    for name in ("decode_step_mfu", "prefill_mfu.hybrid"):
        assert configs.reader(name).read(rec) > 0


def test_a_traced_run_reads_every_listed_metric(monkeypatch):
    """A traced run reads every metric listed for the cell. Its profile is
    read after the window, here for 6 s, in which the decode goes on and
    reaches ``n_tokens``: that is not counted as finished early, since it
    was not inside the window."""
    import jax
    from chipbench import harness
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(harness.tr, "find_xplane", lambda d: d)

    def device_ops(path):
        time.sleep(6.0)
        return {"/device:TPU:0": [("fusion.1", 0, 10 ** 9)]}
    monkeypatch.setattr(harness.tr, "device_ops", device_ops)
    monkeypatch.setattr(harness.tr, "host_marks", lambda path: [])
    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench.run import execute
    ctx = tiny_ctx(n_tokens=400)
    ctx.trace = True
    out = execute(ctx, configs.benchmark(), TINY_DEVICE)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {
        "decode_step_mfu", "prefill_mfu.hybrid", "ckpt_pin_ms.serve",
        "token_gap_p50_ms", "device_idle_share.serve"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_the_control_fails():
    import jax
    from chipbench.control import control_run
    jax.config.update("jax_enable_compilation_cache", False)
    out = control_run(tiny_ctx(), configs.benchmark(), TINY_DEVICE)
    assert all(c["value"] <= c["limit"] for c in out["program"].values())
    assert not out["correct"], out["checks"]
    assert failing(out) == {"token_logit_gap"}


def test_a_resume_that_loses_the_recurrent_state_is_not_correct(
        monkeypatch):
    """The image keeps every leaf, but the restored server starts from
    a zeroed Mamba state: only the resume can see it."""
    import jax.numpy as jnp
    from repro.serve.engine import ServeApp
    start = ServeApp.start

    def restart(self, ctx, restore_state):
        if restore_state is not None:
            cache = {k: ({n: jnp.zeros_like(x) for n, x in v.items()}
                         if "mamba" in k else v)
                     for k, v in restore_state["cache"].items()}
            restore_state = dict(restore_state, cache=cache)
        return start(self, ctx, restore_state)
    monkeypatch.setattr(ServeApp, "start", restart)
    _, out = tiny_run()
    assert not out["correct"]
    assert failing(out) == {"resume_differing"}


def test_an_altered_kv_image_is_not_correct(monkeypatch):
    import jax
    from repro.serve.engine import ServeApp
    orig = ServeApp._materialize

    def altered(snap, batch):
        out = orig(snap, batch)
        cache = {k: (jax.tree.map(lambda x: x + 1, v) if "attn" in k else v)
                 for k, v in out["cache"].items()}
        return dict(out, cache=cache)
    monkeypatch.setattr(ServeApp, "_materialize", staticmethod(altered))
    _, out = tiny_run()
    assert not out["correct"]
    assert "image_leaves_differing" in failing(out)


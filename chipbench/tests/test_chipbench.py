"""CPU tests of the chip benchmark: lookup by name, the FLOP and byte
counts, the trace reduction, the span clock, the refusal to run without a
TPU, the control and the planted faults. No topology is described and no
TPU is touched.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import configs, counts, program  # noqa: E402,F401
from chipbench import trace as tr  # noqa: E402

def bench():
    return configs.benchmark()


# ------------------------------------------------------------ by name

def test_every_entry_is_found_by_name():
    b = bench()
    for c in b["configs"]:
        assert configs.config(c["name"])["name"] == c["name"]
    for w in b["workloads"]:
        wl = configs.workload(w["traffic"])
        assert wl["config"] == w["config"]
        assert hasattr(configs.driver(wl["driver"]), "run")
        configs.reference(configs.config(w["config"])["reference"])
    for m in b["per_layer"]:
        assert hasattr(configs.reader(m["name"]), "read")


def test_a_new_file_is_picked_up_without_edits(tmp_path, monkeypatch):
    here = tmp_path / "chipbench"
    shutil.copytree(configs.HERE, here)
    monkeypatch.setattr(configs, "HERE", here)
    cfg = dict(configs.config("internlm2-1.8b-train"), name="new-model")
    (here / "configs" / "new-model.json").write_text(json.dumps(cfg))
    wl = dict(configs.workload("train-nosave"), name="new-cell",
              config="new-model")
    (here / "workloads" / "new-cell.json").write_text(json.dumps(wl))
    (here / "metrics" / "new_metric.cell.py").write_text(
        "def read(rec):\n    return rec.get('x')\n")
    assert configs.config("new-model")["hidden_size"] == 2048
    assert configs.workload("new-cell")["config"] == "new-model"
    assert configs.reader("new_metric.cell").read({"x": 3.0}) == 3.0
    b = bench()
    b["workloads"].append({"name": "new-cell", "config": "new-model",
                           "traffic": "new-cell", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric.cell", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "model", "moves": "train_tokens_per_s",
                           "workloads": ["new-cell"]})
    b["end_to_end"][0]["workloads"].append("new-cell")
    names = [m["name"] for m in configs.cell_metrics(b, "new-cell",
                                                     "per_layer")]
    assert names == ["new_metric.cell"]
    e2e = [m["name"] for m in configs.cell_metrics(b, "new-cell",
                                                   "end_to_end")]
    assert e2e == ["train_tokens_per_s", "setup_s"]


# ------------------------------------------------------------- counts

def test_counts_match_the_hand_worked_figures():
    train = configs.config("internlm2-1.8b-train")
    serve = configs.config("granite-8b-serve")
    assert round(counts.param_count(train) / 1e6, 1) == 314.9
    assert round(counts.param_count(serve) / 1e9, 2) == 4.13
    assert counts.kv_bytes_per_token(serve) == 73_728        # 73.7 KB
    # state at 10 B/param: bf16 weights, f32 AdamW m and v
    assert round(counts.param_count(train) * 10 / 1e9, 2) == 3.15
    step = counts.train_flops_per_token(train, 2048) * 8 * 2048
    assert round(step / 1e12, 1) == 31.1
    # batch 8 x cache 4096 of KV is 2.42 GB
    assert round(8 * 4096 * counts.kv_bytes_per_token(serve) / 1e9, 2) \
        == 2.42
    cost = counts.decode_step_cost(serve, 8, 0)
    assert cost["bytes"] == counts.param_count(serve) * 2
    need = counts.qsnap_encode_bytes([((512,), "bfloat16")])
    assert need == 512 * 2 + 512 + 2 * 4


def test_peaks_refuse_an_unknown_device():
    from chipbench.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


# -------------------------------------------------------------- trace

def test_union_and_idle_share():
    ops = [("a.1", 0, 10), ("b.2", 5, 10), ("a.3", 30, 10)]
    assert tr.union((s, s + d) for _, s, d in ops) == [(0, 15), (30, 40)]
    assert tr.busy_ns(ops) == 25
    assert tr.busy_ns(ops, lo=10, hi=35) == 10
    assert tr.idle_share(ops, 50) == pytest.approx(0.5)
    assert tr.op_time_ns(ops, r"^a\.") == (20, ["a.1", "a.3"])
    assert tr.top_ops(ops) == [("a", 20e-9), ("b", 10e-9)]
    assert tr.op_kind("%copy.90 = bf16[18,8]{1,0} copy(bf16[18,8] %x.1)") \
        == "copy"
    gaps = tr.idle_gaps(ops, [("bench/x", 14, 20)])
    assert gaps == [("bench/x", 15e-9)]


# --------------------------------------------------------------- spans

def test_spans_convert_to_wall_seconds_by_the_clock_scale():
    from chipbench.harness import Ctx
    from repro.obs.trace import Tracer, use_tracer
    from repro.sim.simtime import active_clock
    args = argparse.Namespace(workload="train-save", seed=1, seconds=1,
                              trace=0)
    ctx = Ctx(args, time.perf_counter())
    with use_tracer(Tracer()) as t:
        ctx.window_mono0 = time.monotonic()
        with t.span("ckpt/pin"):
            time.sleep(0.05)
        ctx.window_mono1 = time.monotonic()
        (sp,) = ctx.spans_in_window("ckpt/pin")
    scale = active_clock().scale
    assert scale != 1.0                    # spans are stamped in paper s
    assert sp["dur_s"] == pytest.approx(0.05, abs=0.02)


# ---------------------------------------------------- no chip, no run

def test_a_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "train-save",
         "--seed", "2147483701", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# ------------------------------------------ tiny runs, control, faults

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=300)

# Limits at the tiny size, set by the cells' own rule between the tiny
# program's readings on the CPU (three seeds: loss at most 2.9e-3, gradient
# 3.2e-3, change 2.8e-3, token gap 6e-3) and the tiny control's (at least
# 7.5e-3, 1.9e-2, 6.9e-3 and 9.4e-2): logits and gradients shrink with the
# width, so the cells' limits, set at the published widths, do not carry
# down to it.
TINY_LIMITS = {"loss_gap": 0.006, "grad_gap": 0.007, "change_gap": 0.008,
               "token_logit_gap": 0.03}
TINY_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_ctx(cell, seed=2147483711, seconds=2.0):
    from chipbench.harness import Ctx
    wl = dict(configs.workload(cell))
    cfg = dict(configs.config(wl["config"]), **TINY)
    if "seq_len" in wl:
        wl.update(batch=2, seq_len=32)
    else:
        wl.update(batch=4, prompt_len=16, cache_len=4096, n_tokens=4000)
    if wl.get("saves"):
        wl["saves"] = dict(wl["saves"], period_s=0.7, first_s=0.2)
    wl["limits"] = {k: TINY_LIMITS.get(k, v)
                    for k, v in wl["limits"].items()}
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return Ctx(args, time.perf_counter(), workload=wl, config=cfg)


def tiny_run(cell, **kw):
    import jax
    from chipbench.run import execute
    jax.config.update("jax_enable_compilation_cache", False)
    ctx = tiny_ctx(cell, **kw)
    out = execute(ctx, bench(), TINY_DEVICE)
    return ctx, out


def failing(out):
    return {n for n, c in out["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", ["train-save", "train-swap", "serve-pin"])
def test_a_sound_tiny_run_is_correct(cell):
    _, out = tiny_run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_the_train_control_fails():
    from chipbench.control import control_run
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    out = control_run(tiny_ctx("train-save"), bench(), TINY_DEVICE)
    assert all(c["value"] <= c["limit"] for c in out["program"].values())
    assert not out["correct"], out["checks"]
    assert failing(out)
    assert set(out["half_batch"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_the_serve_control_fails():
    import jax
    from chipbench.control import control_run
    jax.config.update("jax_enable_compilation_cache", False)
    out = control_run(tiny_ctx("serve-pin"), bench(), TINY_DEVICE)
    assert all(c["value"] <= c["limit"] for c in out["program"].values())
    assert not out["correct"], out["checks"]
    assert failing(out) == {"token_logit_gap"}


def _unchanged(step):
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def _half_batch(step):
    def f(state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(state, half)
    return f


@pytest.mark.parametrize("cell", ["train-save", "train-swap"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_train_step_is_not_correct(cell, fault, monkeypatch):
    from repro.train import trainer
    orig = trainer.make_train_step
    monkeypatch.setattr(trainer, "make_train_step",
                        lambda *a, **k: fault(orig(*a, **k)))
    _, out = tiny_run(cell)
    assert not out["correct"]
    assert failing(out) & {"loss_gap", "grad_gap", "change_gap"}


def test_an_altered_image_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.train.trainer import TrainerApp
    orig = TrainerApp.snapshot_async

    def altered(self, **kw):
        handle = orig(self, **kw)
        inner = handle.resolve

        def resolve():
            snap = inner()
            st = dict(snap["state"], step=snap["state"]["step"] + 1)
            return dict(snap, state=st)
        handle.resolve = resolve
        return handle
    monkeypatch.setattr(TrainerApp, "snapshot_async", altered)
    _, out = tiny_run("train-save")
    assert not out["correct"]
    assert "image_leaves_differing" in failing(out)
    del jnp


@pytest.mark.parametrize("leaf", ["tokens_out", "cache"])
def test_an_altered_snapshot_is_not_correct(leaf, monkeypatch):
    import jax
    from repro.serve.engine import ServeApp
    orig = ServeApp._materialize

    def bump(x):
        return x + jax.numpy.ones_like(x) if isinstance(x, jax.Array) \
            else x + 1

    def altered(snap, batch):
        out = orig(snap, batch)
        return dict(out, **{leaf: jax.tree.map(bump, out[leaf])})
    monkeypatch.setattr(ServeApp, "_materialize", staticmethod(altered))
    _, out = tiny_run("serve-pin")
    assert not out["correct"]
    assert "image_leaves_differing" in failing(out)


def test_an_altered_swap_payload_is_not_correct(monkeypatch):
    from repro.ckpt.plane import PreEncodedChunk
    from repro.train import trainer
    orig = trainer.encode_state_on_device

    def altered(tree, **kw):
        out = orig(tree, **kw)
        leaf = out["params"]["embed"]["embedding"]
        off, shp, chunk = leaf.chunks[0]
        data = bytearray(chunk.data)
        data[-1] = (data[-1] + 1) % 256
        leaf.chunks[0] = (off, shp, PreEncodedChunk(bytes(data),
                                                    chunk.codec))
        return out
    monkeypatch.setattr(trainer, "encode_state_on_device", altered)
    _, out = tiny_run("train-swap")
    assert not out["correct"]
    assert {"payloads_differing", "restored_off_decode"} <= failing(out)


def test_an_altered_token_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.serve import engine
    orig = engine.Engine.decode

    def decode(self, cache, token, pos):
        logits, cache = orig(self, cache, token, pos)
        if int(pos) == 40:
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, cache
    monkeypatch.setattr(engine.Engine, "decode", decode)
    _, out = tiny_run("serve-pin")
    assert not out["correct"]
    assert "token_logit_gap" in failing(out)


def test_a_decode_that_leaves_its_cache_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serve import engine
    orig = engine.Engine.decode

    def decode(self, cache, token, pos):
        logits, _ = orig(self, jax.tree.map(jnp.copy, cache), token, pos)
        return logits, cache
    monkeypatch.setattr(engine.Engine, "decode", decode)
    _, out = tiny_run("serve-pin")
    assert not out["correct"]
    assert "token_logit_gap" in failing(out)

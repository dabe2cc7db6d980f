"""The program's spans on the profiler's clock, and the spans that split
the save path and the hosted train and decode loops.

  * an enabled tracer's span is also a ``jax.profiler`` annotation on the
    thread that ran it, with the span's duration; a disabled one emits
    nothing;
  * a save records one ``ckpt/d2h`` per array shard, and each
    ``ckpt/encode`` splits into ``ckpt/serialize``, ``ckpt/digest`` and
    ``ckpt/codec``, all under their ``ckpt/save``;
  * ``TrainerApp`` records a ``train/step`` (input, dispatch, sync) per
    step time, ``ServeApp`` a ``serve/step`` (dispatch, sync) per decode.
"""
import concurrent.futures as cf
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.ckpt import AsyncCheckpointer, InMemoryStore, save_checkpoint
from repro.ckpt.snapshot import ReadySnapshot
from repro.configs import get_config, reduced
from repro.obs import Tracer, use_tracer
from repro.serve.engine import ServeApp
from repro.sim.simtime import active_clock
from repro.train.trainer import TrainerApp

CFG = dataclasses.replace(reduced(get_config("repro-100m")), dtype="float32")


def _host_events(logdir, name):
    """(plane name, duration s, metadata) of every event of this name on
    a host plane of the one trace under ``logdir``."""
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(p.name, e.duration_ns / 1e9, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name == name]


def _traced_on_worker(tr, logdir, name):
    def work():
        with tr.span(name, cat="test", args={"nbytes": 5, "of": "raw",
                                             "shape": (2, 3)}):
            time.sleep(0.05)

    with jax.profiler.trace(logdir):
        with cf.ThreadPoolExecutor(1) as ex:
            ex.submit(work).result()


def test_span_is_a_profiler_annotation_on_its_thread(tmp_path):
    tr = Tracer()
    _traced_on_worker(tr, str(tmp_path), "test/probe")
    (sp,) = tr.spans(name="test/probe")
    (event,) = _host_events(str(tmp_path), "test/probe")
    plane, dur_s, meta = event
    wall_s = sp.duration * active_clock().scale
    assert abs(dur_s - wall_s) <= 0.1 * wall_s
    # scalar args travel as metadata; the tuple stays in memory only
    assert meta == {"nbytes": 5, "of": "raw"}
    assert sp.args["shape"] == (2, 3)


def test_disabled_tracer_emits_no_annotation(tmp_path):
    tr = Tracer(enabled=False)
    _traced_on_worker(tr, str(tmp_path), "test/silent")
    assert tr.spans() == []
    assert _host_events(str(tmp_path), "test/silent") == []


def _tree():
    return {"w": jnp.arange(1024, dtype=jnp.float32),
            "b": jnp.ones((4, 8), jnp.bfloat16),
            "n": 3}


def _descends(span, root):
    p = span.parent
    while p is not None and p is not root:
        p = p.parent
    return p is root


@pytest.mark.parametrize("blocking", [True, False])
def test_save_splits_into_copy_and_encode_spans(blocking):
    tree = _tree()
    with use_tracer(Tracer()) as tr:
        store = InMemoryStore()
        if blocking:
            save_checkpoint(store, "x", 1, tree, trace_id="t-save")
        else:
            ck = AsyncCheckpointer(store, "x", trace_id="t-save")
            ck.save(1, ReadySnapshot(tree))
            ck.close()
    (save,) = tr.spans(name="ckpt/save")
    assert save.args["blocking"] is blocking
    d2h = tr.spans(name="ckpt/d2h")
    assert sorted(s.args["nbytes"] for s in d2h) == sorted(
        x.nbytes for x in (tree["w"], tree["b"]))
    encodes = tr.spans(name="ckpt/encode")
    assert len(encodes) == 3                     # w, b and the scalar
    # the raw-content digest exists only where a cache keeps it: the
    # async checkpointer's
    digests = {"chunk"} if blocking else {"raw", "chunk"}
    for enc in encodes:
        kids = [s for s in tr.spans() if s.parent is enc]
        assert {s.name for s in kids} == {
            "ckpt/serialize", "ckpt/digest", "ckpt/codec"}
        assert {s.args["of"] for s in kids
                if s.name == "ckpt/digest"} == digests
    for name in ("ckpt/d2h", "ckpt/encode", "ckpt/serialize",
                 "ckpt/digest", "ckpt/codec", "ckpt/upload"):
        for s in tr.spans(name=name):
            assert _descends(s, save), name
            assert s.trace_id == "t-save"


def _run_to_done(app):
    app.start(None, None)
    deadline = time.monotonic() + 120
    while not app.is_done():
        assert time.monotonic() < deadline, "app did not finish"
        time.sleep(0.02)
    app.stop()
    assert app.healthy()


def _children(tr, span):
    return {s.name: s for s in tr.spans() if s.parent is span}


def test_trainer_records_a_step_span_per_step_time():
    with use_tracer(Tracer()) as tr:
        app = TrainerApp(CFG, global_batch=2, seq_len=16, n_steps=3)
        _run_to_done(app)
    steps = tr.spans(name="train/step")
    assert len(steps) == len(app.step_times) == 3
    for st in steps:
        kids = _children(tr, st)
        assert set(kids) == {"train/input", "train/dispatch", "train/sync"}
        assert kids["train/input"].t1 <= kids["train/dispatch"].t0
        assert kids["train/dispatch"].t1 <= kids["train/sync"].t0


def test_serve_records_a_step_span_per_decode():
    n_tokens = 5
    with use_tracer(Tracer()) as tr:
        app = ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                       cache_len=16)
        _run_to_done(app)
    steps = tr.spans(name="serve/step")
    # the prefill gives the first token; each later one is a decode step
    assert sorted(s.args["generated"] for s in steps) == list(
        range(1, n_tokens))
    for st in steps:
        kids = _children(tr, st)
        assert set(kids) == {"serve/dispatch", "serve/sync"}
        assert kids["serve/dispatch"].t1 <= kids["serve/sync"].t0
    assert np.concatenate(app.tokens_out, axis=1).shape == (1, n_tokens)

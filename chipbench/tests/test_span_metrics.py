"""CPU tests of the readers of the program's own spans: each runs against
a fresh tracer filled with synthetic spans under a clock of known scale.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import configs, program  # noqa: E402,F401
from repro.obs.trace import Span, Tracer, use_tracer  # noqa: E402
from repro.sim.simtime import use_clock  # noqa: E402

SCALE = 0.25          # wall seconds per paper second of the test's clock
READERS = ["ckpt_d2h_s.train", "ckpt_d2h_s.serve", "ckpt_encode_s.train",
           "ckpt_encode_s.serve", "train_host_ms", "decode_wait_ms"]


class Clock:
    scale = SCALE


@pytest.fixture
def tr():
    with use_clock(Clock()), use_tracer(Tracer()) as t:
        yield t


def put(tr, name, t0, t1, parent=None, **args):
    """Record a finished span over [t0, t1) wall seconds (stamped, as the
    tracer stamps, in paper seconds)."""
    sp = Span(name, name.split("/")[0], "", t0 / SCALE, args, parent)
    sp.t1 = t1 / SCALE
    tr._record(sp)
    return sp


def save(tr, t0, t1, d2h, encode, blocking=False):
    """A save with its copies under ``ckpt/materialize`` and, per encode
    pool thread, a ``ckpt/encode`` holding the given stage intervals."""
    sv = put(tr, "ckpt/save", t0, t1, blocking=blocking)
    mat = put(tr, "ckpt/materialize", t0, t0 + 0.5, sv)
    for a, b in d2h:
        put(tr, "ckpt/d2h", a, b, mat, nbytes=1)
    for stages in encode:
        enc = put(tr, "ckpt/encode", stages[0][1], stages[-1][2], sv)
        for name, a, b in stages:
            put(tr, name, a, b, enc)
    return sv


def read(name):
    return configs.reader(name).read({})


def test_copies_and_encode_stages_are_unions_across_threads(tr):
    # save 1: copies 2.5 s; encode on two threads, [4, 6.5) + [7, 8): 3.5 s
    save(tr, 0, 10, d2h=[(1, 2), (2, 3.5)],
         encode=[[("ckpt/serialize", 4, 5), ("ckpt/digest", 4.5, 6),
                  ("ckpt/codec", 7, 8)],
                 [("ckpt/serialize", 5.5, 6.5)]])
    # save 2: copies 1.5 s in two overlapping spans; encode 1 s
    save(tr, 20, 30, d2h=[(21, 22), (21.5, 22.5)],
         encode=[[("ckpt/digest", 23, 24)]])
    # save 3: copies 0.5 s; encode 2 s
    save(tr, 40, 50, d2h=[(41, 41.5)], encode=[[("ckpt/codec", 42, 44)]])
    assert read("ckpt_d2h_s.train") == pytest.approx(1.5)
    assert read("ckpt_encode_s.train") == pytest.approx(2.0)
    assert read("ckpt_d2h_s.serve") == read("ckpt_d2h_s.train")
    assert read("ckpt_encode_s.serve") == read("ckpt_encode_s.train")


def test_descendants_of_a_blocking_save_are_left_out(tr):
    save(tr, 0, 10, d2h=[(1, 2)], encode=[[("ckpt/codec", 3, 4)]])
    save(tr, 20, 60, d2h=[(21, 40)], encode=[[("ckpt/codec", 41, 59)]],
         blocking=True)
    put(tr, "ckpt/d2h", 70, 90, nbytes=1)        # under no save at all
    assert read("ckpt_d2h_s.train") == pytest.approx(1.0)
    assert read("ckpt_encode_s.train") == pytest.approx(1.0)


def step(tr, kind, t0, t1, sync):
    st = put(tr, f"{kind}/step", t0, t1)
    put(tr, f"{kind}/dispatch", t0, t0 + 0.001, st)
    put(tr, f"{kind}/sync", t1 - sync, t1, st)
    return st


def test_train_host_ms_reads_steps_that_overlap_an_async_save(tr):
    save(tr, 10, 20, d2h=[(11, 12)], encode=[])
    save(tr, 30, 40, d2h=[(31, 32)], encode=[], blocking=True)
    step(tr, "train", 8, 9, sync=0.5)            # before: left out
    step(tr, "train", 9.5, 10.5, sync=0.7)       # host 0.3 s
    step(tr, "train", 15, 15.4, sync=0.3)        # host 0.1 s
    step(tr, "train", 19.8, 20.3, sync=0.3)      # host 0.2 s
    step(tr, "train", 35, 36, sync=0.1)          # blocking save: left out
    assert read("train_host_ms") == pytest.approx(200.0)


def test_decode_wait_ms_is_the_95th_percentile_of_sync(tr):
    save(tr, 10, 40, d2h=[(11, 30)], encode=[])
    waits = [0.001 * (k + 1) for k in range(100)]
    for k, w in enumerate(waits):
        step(tr, "serve", 10 + 0.2 * k, 10 + 0.2 * k + 0.15, sync=w)
    step(tr, "serve", 45, 47, sync=1.9)          # after the save: left out
    assert read("decode_wait_ms") == pytest.approx(
        1e3 * float(np.percentile(waits, 95)))


def test_readings_are_in_wall_units(tr):
    save(tr, 0, 8, d2h=[(1, 3)], encode=[[("ckpt/serialize", 4, 7)]])
    step(tr, "train", 2, 2.5, sync=0.2)
    (d2h,) = tr.spans(name="ckpt/d2h")
    assert d2h.duration == pytest.approx(2 / SCALE)      # paper seconds
    assert read("ckpt_d2h_s.train") == pytest.approx(2.0)
    assert read("ckpt_encode_s.train") == pytest.approx(3.0)
    assert read("train_host_ms") == pytest.approx(300.0)


@pytest.mark.parametrize("name", READERS)
def test_no_async_save_reads_none(tr, name):
    assert read(name) is None
    save(tr, 0, 10, d2h=[(1, 2)], encode=[[("ckpt/codec", 3, 4)]],
         blocking=True)
    step(tr, "train", 2, 3, sync=0.5)
    step(tr, "serve", 2, 3, sync=0.5)
    assert read(name) is None

"""Reduction of a profiler trace to device metrics.

``device_ops`` reads the ``.xplane.pb`` the JAX profiler writes and keeps,
for each TPU, the events of its "XLA Ops" line: the operations that ran on
the device, with start and duration in nanoseconds. Busy time is the union
of those intervals; the idle share is 1 - busy / window. ``host_marks``
keeps the benchmark's own host annotations (names starting ``bench/``), by
which ``idle_gaps`` names what the host was doing in each long gap.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]              # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MARK_PREFIX = "bench/"


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {files}")
    return files[0]


def _planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def device_ops(path: str) -> Dict[str, List[Event]]:
    """plane name -> its device operations, for every TPU plane."""
    out: Dict[str, List[Event]] = {}
    for plane in _planes(path):
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events]
    if not out:
        raise RuntimeError(f"no TPU plane with an {OPS_LINE!r} line in "
                           f"{path}")
    return out


def host_marks(path: str) -> List[Event]:
    """The benchmark's own host annotations, from every host thread."""
    out = []
    for plane in _planes(path):
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events
                       if e.name.startswith(MARK_PREFIX))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(ops: Sequence[Event], lo: Optional[int] = None,
            hi: Optional[int] = None) -> int:
    """Nanoseconds in which some operation ran, clipped to [lo, hi)."""
    total = 0
    for s, e in union((s, s + d) for _, s, d in ops):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        total += max(0, e - s)
    return total


def idle_share(ops: Sequence[Event], window_ns: int) -> float:
    if window_ns <= 0:
        raise ValueError("empty window")
    return 1.0 - busy_ns(ops) / window_ns


def op_time_ns(ops: Sequence[Event], pattern: str) -> Tuple[int, List[str]]:
    """Total device time of the operations whose name matches ``pattern``
    (a regular expression), and the distinct names that matched."""
    rx = re.compile(pattern)
    hit = [(n, d) for n, _, d in ops if rx.search(n)]
    return sum(d for _, d in hit), sorted({n for n, _ in hit})


def op_kind(name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%copy.90 = bf16[...] copy(...)`` -> ``copy``."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))


def top_ops(ops: Sequence[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The kinds of operation that took most device time, in seconds."""
    tot: Dict[str, int] = {}
    for name, _, d in ops:
        key = op_kind(name)
        tot[key] = tot.get(key, 0) + d
    return [(k, v / 1e9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: Sequence[Event], marks: Sequence[Event],
              n: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between device operations, each named by the
    innermost benchmark annotation that covers most of it, else by the
    operation that ends it."""
    busy = union((s, s + d) for _, s, d in ops)
    starts = {s: name for name, s, _ in ops}
    gaps = []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        best, cover = None, 0
        for name, ms, md in marks:
            c = min(e0 + (s1 - e0), ms + md) - max(e0, ms)
            if c > cover or (c == cover and c > 0 and md < best[1]):
                best, cover = (name, md), c
        label = best[0] if best and cover * 2 >= s1 - e0 else \
            f"before {op_kind(starts.get(s1, '?'))}"
        gaps.append((label, (s1 - e0) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])[:n]

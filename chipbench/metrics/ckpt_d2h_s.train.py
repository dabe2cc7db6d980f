"""Median, over the window's async saves, of the union of their
``ckpt/d2h`` spans (each device-to-host copy of a saved array), wall s.

The other readers of the program's own spans take the helpers below from
this file. They read the process's ``obs`` tracer, whose spans are stamped
in paper seconds, and convert them to wall seconds by the installed
clock's ``scale``. A save is one of the window's when it is a ``ckpt/save``
span with ``blocking`` False: set-up makes no save, and the save made
after the window is blocking.
"""
import statistics

from chipbench import trace


def spans():
    """Every finished span as (span, start, end), in wall seconds."""
    from repro.obs.trace import tracer
    from repro.sim.simtime import active_clock
    scale = active_clock().scale
    return [(s, s.t0 * scale, s.t1 * scale) for s in tracer().spans()]


def async_saves(rows):
    return [r for r in rows if r[0].name == "ckpt/save"
            and r[0].args.get("blocking") is False]


def descends(span, root) -> bool:
    p = span.parent
    while p is not None:
        if p is root:
            return True
        p = p.parent
    return False


def union_s(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    return sum(b - a for a, b in trace.union(intervals))


def per_save(names):
    """For each async save with descendants named in ``names``, the
    length of the union of those descendants, in wall seconds."""
    rows = spans()
    out = []
    for save, _, _ in async_saves(rows):
        iv = [(a, b) for s, a, b in rows
              if s.name in names and descends(s, save)]
        if iv:
            out.append(union_s(iv))
    return out


def during_saves(name):
    """(span, start, end) of the spans of this name that overlap an
    async save, with each one's children by name."""
    rows = spans()
    saves = [(a, b) for _, a, b in async_saves(rows)]
    kids = {}
    for s, a, b in rows:
        if s.parent is not None:
            kids.setdefault(id(s.parent), {})[s.name] = (a, b)
    return [(r, kids.get(id(r[0]), {})) for r in rows
            if r[0].name == name
            and any(r[1] < d and r[2] > c for c, d in saves)]


def read(rec):
    d = per_save({"ckpt/d2h"})
    return statistics.median(d) if d else None

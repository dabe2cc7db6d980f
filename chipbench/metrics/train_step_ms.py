"""Median host time of the window's train steps (``TrainerApp.step_times``:
each step ends in ``float(loss)`` and ``block_until_ready``), ms."""
import statistics


def read(rec):
    d = rec.get("step_times") or []
    return 1e3 * statistics.median(d) if d else None

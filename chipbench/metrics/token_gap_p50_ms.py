"""Median gap between consecutive decode steps of the hosted server in
the window (the series whose tail is ``token_gap_p95_ms``), ms."""
import statistics


def read(rec):
    g = rec.get("gaps") or []
    return 1e3 * statistics.median(g) if g else None

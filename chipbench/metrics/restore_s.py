"""Median restore of the window's resumes: ``ckpt/restore`` spans (fetch,
decode, assemble, H2D), wall s."""
import statistics


def read(rec):
    d = [s["dur_s"] for s in rec.get("spans", {}).get("ckpt/restore", [])]
    return statistics.median(d) if d else None

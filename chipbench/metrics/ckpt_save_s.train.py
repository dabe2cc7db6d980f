"""Median duration of the window's async saves: ``ckpt/save`` spans
(materialize, encode, upload, commit on the writer thread), wall s."""
import statistics


def read(rec):
    d = [s["dur_s"] for s in rec.get("spans", {}).get("ckpt/save", [])
         if s["args"].get("blocking") is False]
    return statistics.median(d) if d else None

"""Median pin stall of the window's saves: ``ckpt/pin`` spans (the
control plane's capture under the coordinator lock), in wall ms."""
import statistics


def read(rec):
    d = [s["dur_s"] for s in rec.get("spans", {}).get("ckpt/pin", [])]
    return 1e3 * statistics.median(d) if d else None

"""The whole decode step's share of its roofline: the larger of its FLOPs
over the bf16 peak and its needed bytes (weights plus the filled cache
positions) over HBM bandwidth, divided by the window's mean gap, in %."""


def read(rec):
    gaps = rec.get("gaps") or []
    cost = rec.get("decode_cost")
    if not gaps or not cost:
        return None
    p = rec["peaks"]
    least = max(cost["flops"] / p["bf16_flops"],
                cost["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / (sum(gaps) / len(gaps))

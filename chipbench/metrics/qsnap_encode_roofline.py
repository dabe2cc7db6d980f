"""The int8 ``qsnap`` encode's share of its roofline: bytes the encodes of
the window need (each float leaf read once in its stored dtype, plus int8
codes and f32 scales) over the device time of the kernel's operations in
the trace, over HBM bandwidth, in %. Memory bound: one pass, no reuse."""
from chipbench import trace as tr

PATTERN = r"quant_kernel|qsnap"


def read(rec):
    t, need = rec.get("trace"), rec.get("qsnap_bytes")
    if not t or not need:
        return None
    ns, names = tr.op_time_ns(t["ops"], PATTERN)
    if ns == 0:
        return None
    rec.setdefault("matched_ops", {})["qsnap_encode_roofline"] = names
    return 100.0 * need / (ns / 1e9) / rec["peaks"]["hbm_bytes_per_s"]

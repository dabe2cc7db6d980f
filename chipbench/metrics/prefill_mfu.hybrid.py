"""The set-up prefill's share of the chip's bf16 peak: the prefill's FLOPs
(``hybrid_counts.prefill_flops``: projections, causal attention and the
recurrence of every prompt) over the wall time of the program's
``serve/prefill`` span, which holds one ``serve/prefill_row`` per row,
in %."""


def read(rec):
    sp = rec.get("prefill")
    flops = rec.get("prefill_flops")
    if not sp or not flops or sp["dur_s"] <= 0:
        return None
    return 100.0 * flops / sp["dur_s"] / rec["peaks"]["bf16_flops"]

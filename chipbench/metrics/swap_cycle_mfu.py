"""The whole swap cycle's share of the chip's bf16 peak: the model FLOPs
of the one train step that ends each cycle (the encode, copies and
restore do next to no arithmetic) over the mean cycle time, in %."""


def read(rec):
    cycle = rec.get("swap_cycle_s")
    if not cycle:
        return None
    return 100.0 * rec["train_flops_per_step"] / cycle \
        / rec["peaks"]["bf16_flops"]

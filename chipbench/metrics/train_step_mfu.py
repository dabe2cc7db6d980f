"""The whole train step's share of the chip's bf16 peak: model FLOPs per
token (6 x matmul params plus full S x S attention; recompute not counted)
times the window's tokens per second, over the peak, in %."""


def read(rec):
    rate = rec.get("train_tokens_per_s")
    if not rate:
        return None
    return 100.0 * rec["train_flops_per_token"] * rate \
        / rec["peaks"]["bf16_flops"]

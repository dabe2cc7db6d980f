"""Median, over the window's async saves, of the union across the encode
pool's threads of their ``ckpt/serialize``, ``ckpt/digest`` and
``ckpt/codec`` spans: the host's encode work, wall s."""
import statistics

from chipbench import configs

STAGES = {"ckpt/serialize", "ckpt/digest", "ckpt/codec"}


def read(rec):
    d = configs.reader("ckpt_d2h_s.train").per_save(STAGES)
    return statistics.median(d) if d else None

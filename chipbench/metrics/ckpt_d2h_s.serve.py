"""Median, over the window's async saves, of the union of their
``ckpt/d2h`` spans (each device-to-host copy of a saved array), wall s;
read as ``ckpt_d2h_s.train`` reads it."""
from chipbench import configs


def read(rec):
    return configs.reader("ckpt_d2h_s.train").read(rec)

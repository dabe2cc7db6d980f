"""Median, over the window's async saves, of the union across the encode
pool's threads of their ``ckpt/serialize``, ``ckpt/digest`` and
``ckpt/codec`` spans, wall s; read as ``ckpt_encode_s.train`` reads it."""
from chipbench import configs


def read(rec):
    return configs.reader("ckpt_encode_s.train").read(rec)

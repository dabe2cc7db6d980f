"""Mean, over the ``train/step`` spans that overlap an async save, of the
step's duration less its ``train/sync`` child: the host's own time per
step while a save runs beside it, wall ms."""
from chipbench import configs


def read(rec):
    steps = configs.reader("ckpt_d2h_s.train").during_saves("train/step")
    d = [(b - a) - (k["train/sync"][1] - k["train/sync"][0])
         for (_, a, b), k in steps if "train/sync" in k]
    return 1e3 * sum(d) / len(d) if d else None

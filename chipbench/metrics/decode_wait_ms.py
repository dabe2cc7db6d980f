"""95th percentile, over the ``serve/step`` spans that overlap an async
save, of their ``serve/sync`` child: the decode thread's wait for the
step's cache and its token to reach the host, wall ms."""
import numpy as np

from chipbench import configs


def read(rec):
    steps = configs.reader("ckpt_d2h_s.train").during_saves("serve/step")
    d = [k["serve/sync"][1] - k["serve/sync"][0]
         for _, k in steps if "serve/sync" in k]
    return 1e3 * float(np.percentile(d, 95)) if d else None

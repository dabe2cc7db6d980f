"""Record the small chip trace the trace-reduction test reads.

    python -m chipbench.tests.record_trace

Runs a jitted matmul and the int8 ``qsnap`` encode of one leaf under the
profiler and writes ``chipbench/testdata/small.xplane.pb``, printing the
planes and lines it holds. Needs a TPU.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time

from chipbench import configs, program  # noqa: F401
from chipbench import trace as tr

OUT = configs.HERE / "testdata" / "small.xplane.pb"


def main() -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels.qsnap import qsnap_encode_chunks
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    leaf = jnp.ones((4, 256 * 256), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    mm(x).block_until_ready()
    qsnap_encode_chunks([leaf])
    (configs.ROOT / "chipbench_out").mkdir(exist_ok=True)
    d = tempfile.mkdtemp(dir=str(configs.ROOT / "chipbench_out"))
    jax.profiler.start_trace(d)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/matmul"):
            mm(x).block_until_ready()
        time.sleep(0.01)
    with jax.profiler.TraceAnnotation("bench/encode"):
        qsnap_encode_chunks([leaf])
    jax.profiler.stop_trace()
    path = tr.find_xplane(d)
    OUT.parent.mkdir(exist_ok=True)
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    for plane in tr._planes(str(OUT)):
        print(plane.name, [(ln.name, sum(1 for _ in ln.events))
                           for ln in plane.lines])
    for dev, ops in tr.device_ops(str(OUT)).items():
        print(dev, len(ops), tr.top_ops(ops), tr.busy_ns(ops))
    print("marks", tr.host_marks(str(OUT)))
    print("bytes", OUT.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())

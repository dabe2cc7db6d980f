"""Shared test helpers.

NOTE: no XLA_FLAGS manipulation here — smoke tests must see the real single
CPU device. Multi-device tests (resharding, the sharded train step) spawn
subprocesses that set --xla_force_host_platform_device_count themselves.
"""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture
def sim_clock():
    """Run a test on the discrete-event virtual clock (repro.sim).

    Installs a fresh SimClock process-wide for the duration of the test:
    every ``sim_sleep``, store latency, daemon poll and settle wait in the
    control plane advances virtual time instantly instead of wall
    sleeping.  Suites opt in with a module-local autouse shim::

        @pytest.fixture(autouse=True)
        def _virtual_time(sim_clock):
            yield

    Teardown closes the clock (wakes every sleeper) *after* the test's own
    service fixtures have shut down, then restores the wall clock.
    """
    from repro.sim import SimClock, install_clock
    clk = SimClock()
    prev = install_clock(clk)
    try:
        yield clk
    finally:
        clk.close()
        install_clock(prev)


def make_batch(cfg, model, B, S, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    toks = lambda b, s: rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks(B, S)),
             "targets": jnp.asarray(toks(B, S))}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_len, cfg.d_model)),
            model.dtype) * 0.02
    elif cfg.frontend is not None:
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_len, cfg.d_model)),
            model.dtype) * 0.02
        batch["tokens"] = jnp.asarray(toks(B, S - cfg.frontend_len))
    return batch


def run_subprocess(code: str, devices: int = 8, timeout: int = 560) -> str:
    """Run python code in a subprocess with N forced host devices, pinned
    to the CPU so the child never claims an accelerator."""
    prelude = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n")
    r = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout

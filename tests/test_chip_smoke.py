"""chip_smoke.py's phases, run in-process on the CPU at reduced size.

The same checks as on the chip: a bit-exact restart, device-encoded QS01
payloads byte-identical to the host codec (through the ``ref`` encoder
here), identical tokens after a mid-decode suspend and resume, and no
error counter rising. The resharding phase runs on four host devices in a
child process.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config, reduced
from tests.conftest import run_subprocess

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def service(smoke, tmp_path):
    svc = smoke.make_service(tmp_path)
    try:
        yield svc
    finally:
        svc.shutdown()


CFG = reduced(get_config("repro-100m"))


def test_train_restart_and_swap_out(smoke, service):
    health = smoke.Health()
    cid = smoke.train_phase(service, health, CFG, global_batch=2,
                            seq_len=32, n_steps=8, restart_at=4,
                            period_s=0.2)
    assert smoke.swap_phase(service, health, cid) == "ref"
    health.check(service, "test")


def test_serve_suspend_resume_in_fresh_app(smoke, service):
    health = smoke.Health()
    smoke.serve_phase(service, health, CFG, batch=2, prompt_len=8,
                      n_tokens=16, suspend_at=4, token_delay_s=0.05)
    health.check(service, "test")


def test_health_fails_on_a_counted_error(smoke, service):
    from repro.obs.telemetry import MetricsRegistry, use_registry
    with use_registry(MetricsRegistry()) as reg:
        health = smoke.Health()
        reg.inc("appmgr.op_errors", note="RuntimeError: injected")
        with pytest.raises(smoke.SmokeFailure, match="appmgr.op_errors"):
            health.check(service, "test")


def test_main_refuses_a_cpu_platform(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                    # no result line
    assert "'cpu'" in out.err


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache goes to
    the fixed, git-ignored .jax_cache/ of the checkout."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           env.get("PYTHONPATH", "")]))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from repro.launch.cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert out.stdout.split() == [want, want]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_reshard_phase_on_four_host_devices():
    out = run_subprocess(f"""
    import importlib.util, pathlib, tempfile
    import jax
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs import get_config, reduced
    with tempfile.TemporaryDirectory() as d:
        smoke.reshard_phase(reduced(get_config("repro-100m")), jax.devices(),
                            pathlib.Path(d), global_batch=4, seq_len=32)
    """, devices=4)
    assert "restored_bitexact=True" in out

"""The driver-graded entry points must be hermetic against accelerator state.

JAX backend discovery (``jax.devices("cpu")`` included) initializes *every*
registered platform, and only one process at a time may hold the TPU — so
``dryrun_multichip``, a CPU-mesh correctness check, pins the cpu platform
before the first backend init. These tests run the real entry point in
fresh subprocesses (backend init is process-global, so in-process tests
can't exercise the pin) and assert:

1. the cpu-platform pin is applied before the first backend init, so no
   non-cpu plugin is ever discovered, and
2. the full dryrun passes end-to-end from a cold process with NO
   environment hints (no JAX_PLATFORMS, no pre-set XLA_FLAGS).

Mirrors the obligation of the reference's 5-server cluster tests
(manager/src/test/java/io/atomix/AtomixClientServerTest.java) running
without real network hardware.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    return env


def test_dryrun_pins_cpu_platform_before_backend_init():
    code = (
        "import __graft_entry__ as g\n"
        "import jax\n"
        "g.dryrun_multichip(2)\n"
        "assert jax.config.jax_platforms == 'cpu', jax.config.jax_platforms\n"
        "plats = {d.platform for d in jax.devices()}\n"
        "assert plats == {'cpu'}, plats\n"
        "print('PINNED-OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "PINNED-OK" in out.stdout


def test_dryrun_full_eight_device_mesh_cold_process():
    code = "import __graft_entry__ as g; g.dryrun_multichip(8); print('DRYRUN-OK')"
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
        capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr
    assert "DRYRUN-OK" in out.stdout

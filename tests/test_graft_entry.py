"""Driver-contract regression tests for __graft_entry__.

Round 1's driver dryrun failed (MULTICHIP_r01.json rc=1) because default-
backend ops inside the sharded engine's init dispatched to a broken TPU
client even though the mesh was CPU. These tests run the dryrun the way the
DRIVER does — a clean subprocess that does NOT inherit conftest's
JAX_PLATFORMS=cpu — and assert the accelerator backend is never even
initialized, which is the strongest available proof that a broken
accelerator client cannot break the dryrun.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_PLATFORM_NAME")}
    env.update(extra)
    return env


def _run(code: str, env) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


DRYRUN_CODE = """
import __graft_entry__
__graft_entry__.dryrun_multichip(8)
from jax._src import xla_bridge
initialized = sorted(xla_bridge._backends)
assert initialized == ["cpu"], (
    "dryrun touched non-cpu backends: %r" % (initialized,))
print("BACKENDS_OK", initialized)
"""


def test_dryrun_multichip_clean_subprocess_driver_env():
    """Driver shape: XLA_FLAGS set by the invoker, JAX_PLATFORMS unset
    (on a chip host the default backend is the TPU — only the dryrun's
    in-process config update keeps the accelerator out)."""
    env = _clean_env(XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = _run(DRYRUN_CODE, env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip ok" in proc.stdout
    assert "BACKENDS_OK" in proc.stdout


def test_dryrun_multichip_no_flags_at_all():
    """No XLA_FLAGS either: dryrun must provision its own virtual CPU
    devices before the cpu backend initializes."""
    proc = _run(DRYRUN_CODE, _clean_env())
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip ok" in proc.stdout
    assert "BACKENDS_OK" in proc.stdout

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.mesh import cpu_child_env

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def run_multidevice(script: str, n_devices: int = 8, timeout: int = 600):
    """Run a python snippet in a subprocess with N forced host devices.

    Tests and benches in-process must see 1 device (per the dry-run contract),
    so anything needing a mesh runs out-of-process, pinned to the CPU
    (``repro.launch.mesh.cpu_child_env``).
    """
    env = dict(os.environ, **cpu_child_env(n_devices))
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed\n--- stdout ---\n{proc.stdout}"
            f"\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def multidevice():
    return run_multidevice


@pytest.fixture
def force_head_block(monkeypatch):
    """Shrink the SSD kernels' VMEM budget until a pass takes ``hb`` heads a
    grid step: ``force(hb, heads_per_group, p, n, chunk, backward=False)``."""
    from repro.kernels import ssd_scan as S

    def force(hb, heads_per_group, p, n, chunk, backward=False):
        monkeypatch.setattr(S, "VMEM_BUDGET",
                            S.vmem_bytes(hb, p, n, chunk, backward))
        assert S.ssd_head_block(heads_per_group, p, n, chunk, backward) == hb

    return force

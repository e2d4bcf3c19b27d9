"""`chip_smoke.py`'s CPU rehearsal runs every phase at smoke size, on one
device and on a four-device mesh, and without ``--rehearse`` the script
refuses a machine that has no TPU.  Each run is a subprocess: the mesh
needs devices this process does not have, and the script turns on the
persistent compile cache, which here goes to a temporary directory."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(tmp_path, *args, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, str(SCRIPT), *args],
                          capture_output=True, text=True, timeout=600,
                          env=env)


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_every_phase(tmp_path, chips):
    out = _run(tmp_path, "--rehearse", "--chips", str(chips),
               devices=chips)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": chips}}
    phases = ("[sharded]",) if chips == 4 else ("[train]", "[resume]",
                                                 "[serve]")
    for phase in phases:
        assert any(line.startswith(phase) for line in lines), phase
    assert not any(p.name.startswith("chip_smoke_")
                   for p in tmp_path.iterdir())     # data dir removed


def test_refuses_without_a_tpu(tmp_path):
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout

"""scripts/realdev_soak.py without a chip: it fails, and leaves evidence.

The real-device endurance leg (exporter on the live chip -> daemon file
backend) can only run where a TPU is attached. Anywhere else it must exit
non-zero AND write a `"failed": true` artifact naming the reason: a stale
artifact from a prior run masquerading as this run's result, or an exit 0
with nothing measured, would both read as a soak that passed.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_no_tpu_fails_and_writes_artifact(tmp_path):
    artifact = tmp_path / "realdev.json"
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "scripts/realdev_soak.py"),
         "5", str(artifact)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=str(REPO_ROOT))
    assert proc.returncode != 0, proc.stdout
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] is True
    on_disk = json.loads(artifact.read_text())
    assert on_disk["failed"] is True
    assert "no TPU device" in on_disk["reason"]
    assert "JAX_PLATFORMS=cpu" in on_disk["reason"]

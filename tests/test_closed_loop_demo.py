"""Smoke test for examples/closed_loop_demo.sh — the one-command
daemon -> telemetry -> anomaly rule -> auto-capture -> summary flow the
README/demo documentation promises."""

import os
import signal
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


from conftest import slow_lane  # noqa: E402


@slow_lane
def test_demo_script_end_to_end(cpp_build, tmp_path):
    # New session so a hang can be killed as a whole process group — the
    # script's daemon/app children must never outlive the test. PYTHON and
    # JAX_PLATFORMS keep the subprocess on this interpreter and on the CPU.
    proc = subprocess.Popen(
        [str(REPO_ROOT / "examples" / "closed_loop_demo.sh"),
         str(tmp_path / "work")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=str(REPO_ROOT), start_new_session=True,
        env={
            **os.environ,
            "PYTHON": sys.executable,
            "JAX_PLATFORMS": "cpu",
        },
    )
    try:
        out, _ = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise AssertionError(f"demo hung; output so far:\n{out}")
    assert proc.returncode == 0, out
    assert "trigger 1 installed" in out
    assert "auto-captured trace manifest" in out
    assert "plane" in out  # summarizer ran on the fired capture
    fired = list((tmp_path / "work").glob("anomaly_trig1_*"))
    assert fired, out

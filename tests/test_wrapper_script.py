"""Smoke test for scripts/run_with_dynolog.sh: daemon starts alongside the
wrapped command, JSON metric lines land in the log file, daemon is torn
down when the command exits (reference run_with_dyno_wrapper.sh flow)."""

import json
import os
import subprocess
import sys
import uuid
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def test_wrapper_runs_job_with_daemon(cpp_build, tmp_path):
    log_file = tmp_path / "metrics.jsonl"
    env = {
        **os.environ,
        "DYNOLOG_PORT": "0",
        "DYNOLOG_ENDPOINT": f"wrap_test_{uuid.uuid4().hex[:8]}",
        "DYNOLOG_LOG_FILE": str(log_file),
        # The wrapper derives the daemon path from the repo layout; the
        # test build dir is the standard one so no override needed.
    }
    proc = subprocess.run(
        [
            "bash",
            str(REPO_ROOT / "scripts" / "run_with_dynolog.sh"),
            sys.executable,
            "-c",
            # The "job": wait long enough for one kernel-collector tick
            # (interval flag defaults to 60s — the wrapper doesn't override
            # it, so rely on the first immediate tick).
            "import time; time.sleep(3); print('job done')",
        ],
        capture_output=True,
        text=True,
        timeout=60,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr
    assert "job done" in proc.stdout
    # First collector tick fires immediately at startup: the JSON log file
    # must exist with at least one parseable metric line.
    assert log_file.exists(), proc.stderr
    lines = [l for l in log_file.read_text().splitlines() if l.strip()]
    assert lines, "no metric lines written"
    # The kernel row is among them (the TPU monitor, up on its named
    # backend and waiting for a runtime, logs rows of its own).
    samples = [json.loads(line) for line in lines]
    assert any("cpu_util" in s or "uptime" in s for s in samples), samples

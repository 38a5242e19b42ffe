#!/usr/bin/env python3
"""Real-device endurance leg: the exporter on the live chip feeding
dynologd's file backend, sampled for footprint + row liveness.

The CI soak (tests/test_soak.py) churns captures against fake metric
sources; here the metric source is the real chip, read by
dynolog_tpu.exporter. A chip belongs to one process, so the exporter child
is this soak's one JAX process: it holds the (idle) chip for the run, and
this script and the daemon stay off JAX. Run it on a host with no job.
Reference posture anchor: the always-on daemon runs for days against live
devices (/root/reference/README.md:17,28).

Usage: python scripts/realdev_soak.py [seconds] [artifact.json]
Exits non-zero, with the reason printed and in the artifact, when the
exporter finds no TPU device: there is no soak of a chip without one.
"""

import json
import os
import signal
import subprocess
import sys
import time
import uuid
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _fail(artifact, reason: str) -> int:
    """Every exit leaves the same evidence a run would: a printed JSON
    line AND the artifact file (a stale artifact from a prior run would
    otherwise masquerade as this run's result)."""
    out = {"failed": True, "reason": reason}
    print(json.dumps(out))
    if artifact:
        Path(artifact).write_text(json.dumps(out, indent=1) + "\n")
    return 1


def _reap(proc, sig=signal.SIGTERM) -> None:
    """SIGTERM then KILL: a stuck child must not void the soak's
    results (TimeoutExpired out of the finally block would), and must not
    outlive the script holding the chip."""
    if proc.poll() is not None:
        return
    proc.send_signal(sig)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=5)


def main() -> int:
    seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 2700
    artifact = sys.argv[2] if len(sys.argv) > 2 else None

    work = Path("/tmp") / f"realdev_soak_{uuid.uuid4().hex[:8]}"
    work.mkdir()
    snap = work / "snap.json"
    jlog = work / "daemon_metrics.jsonl"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p)

    samples = []  # (t, rss_kb, threads, fds)
    daemon_exit = None
    with open(work / "exporter.log", "w") as exporter_log, \
            open(work / "daemon.log", "w") as daemon_log:
        exporter = subprocess.Popen(
            [sys.executable, "-m", "dynolog_tpu.exporter",
             f"--path={snap}", "--interval-s=2"],
            cwd=str(REPO), env=env,
            stdout=subprocess.DEVNULL, stderr=exporter_log)
        daemon = None
        try:
            # The file backend (deliberately) fails closed when the
            # snapshot path is absent at daemon startup; the exporter's
            # first write lands only after jax backend init.
            deadline = time.time() + 150
            while not snap.exists() and time.time() < deadline:
                if exporter.poll() is not None:
                    return _fail(
                        artifact, "exporter exited during init with code "
                        f"{exporter.returncode} (see {work}/exporter.log)")
                time.sleep(1)
            if not snap.exists():
                return _fail(artifact, "no exporter snapshot within 150s")
            if not json.loads(snap.read_text())["devices"]:
                return _fail(
                    artifact, "the exporter found no TPU device "
                    "(JAX_PLATFORMS="
                    f"{os.environ.get('JAX_PLATFORMS', '(unset)')})")

            daemon = subprocess.Popen(
                [str(REPO / "build/src/dynologd"), "--port=0",
                 "--enable_tpu_monitor", "--tpu_metric_backend=file",
                 f"--tpu_metrics_file={snap}",
                 "--tpu_monitor_reporting_interval_s=2",
                 "--kernel_monitor_reporting_interval_s=5",
                 f"--json_log_file={jlog}", "--nouse_JSON"],
                stdout=subprocess.DEVNULL, stderr=daemon_log)
            t0 = time.time()
            while time.time() - t0 < seconds:
                time.sleep(5)
                if daemon.poll() is not None:
                    # A daemon that died mid-soak must not read as a
                    # clean short soak.
                    daemon_exit = daemon.returncode
                    break
                status = Path(f"/proc/{daemon.pid}/status").read_text()
                rss = int(next(l for l in status.splitlines()
                               if l.startswith("VmRSS")).split()[1])
                thr = int(next(l for l in status.splitlines()
                               if l.startswith("Threads")).split()[1])
                fds = len(os.listdir(f"/proc/{daemon.pid}/fd"))
                samples.append((round(time.time() - t0, 1), rss, thr, fds))
        finally:
            if daemon is not None:
                _reap(daemon)
            _reap(exporter)
    if daemon_exit is not None:
        return _fail(
            artifact, f"dynologd exited with code {daemon_exit} after "
            f"{samples[-1][0] if samples else 0}s of {seconds}s "
            f"(see {work}/daemon.log)")

    # Row liveness from the daemon's JSON log: per-device rows carry
    # entity "tpu<N>" plus bare metric keys; an outage tick carries
    # tpu_error (the reference's blank-value→dcgm_error posture).
    import re

    entity = re.compile(r"^tpu\d+$")
    live_rows = error_rows = 0
    with open(jlog) as f:
        for line in f:
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not entity.match(str(row.get("entity", ""))):
                continue
            if "tpu_error" in row:
                error_rows += 1
            else:
                live_rows += 1

    def slope(points):
        # Self-contained least squares over (x, y) pairs — taking the
        # pairs (not a parallel list) makes a filtered series safe.
        n = len(points)
        if n < 3:
            return None
        xbar = sum(x for x, _ in points) / n
        ybar = sum(y for _, y in points) / n
        denom = sum((x - xbar) ** 2 for x, _ in points) or 1.0
        return sum((x - xbar) * (y - ybar) for x, y in points) / denom

    out = {
        "failed": False,
        "soak_seconds": round(time.time() - t0, 1),
        "backend": "file (real-device exporter, 2s cadence)",
        "samples": len(samples),
        "live_tpu_rows": live_rows,
        "tpu_error_rows": error_rows,
        "rss_first_kb": samples[0][1] if samples else None,
        "rss_last_kb": samples[-1][1] if samples else None,
        "rss_slope_kb_per_s": (
            round(slope([(s[0], s[1]) for s in samples]), 4)
            if len(samples) >= 3 else None),
        "threads_min": min(s[2] for s in samples) if samples else None,
        "threads_max": max(s[2] for s in samples) if samples else None,
        "fd_min": min(s[3] for s in samples) if samples else None,
        "fd_max": max(s[3] for s in samples) if samples else None,
        "workdir": str(work),
    }
    print(json.dumps(out))
    if artifact:
        Path(artifact).write_text(json.dumps(out, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

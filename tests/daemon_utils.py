"""Helpers for spawning dynologd / dyno in integration tests."""

from __future__ import annotations

import json
import os
import select
import socket
import struct
import subprocess
import time
import uuid
from dataclasses import dataclass


@dataclass
class Daemon:
    proc: subprocess.Popen
    port: int
    endpoint: str
    prometheus_port: int | None = None
    relay_port: int | None = None  # --relay fleet-ingest listener

    def rpc(self, request: dict) -> dict | None:
        """Length-prefixed JSON RPC round trip (the dyno CLI wire format)."""
        with socket.create_connection(("localhost", self.port), timeout=5) as s:
            body = json.dumps(request).encode()
            s.sendall(struct.pack("<i", len(body)) + body)
            header = _read_exact(s, 4)
            if header is None:
                return None
            (length,) = struct.unpack("<i", header)
            data = _read_exact(s, length)
            return json.loads(data) if data is not None else None


def assert_status_ok(reply: dict | None) -> None:
    """getStatus answered by a live daemon: status 1 plus the build
    identity the verb carries (version, wire proto)."""
    assert reply is not None, "no reply to getStatus"
    assert reply["status"] == 1, reply
    assert "version" in reply and "proto" in reply, reply


def _read_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def start_daemon(
    bin_dir, extra_flags=(), kernel_interval_s=1, endpoint=None, env=None
) -> Daemon:
    """`env` adds/overrides environment variables for the daemon process
    (e.g. DYNO_FAILPOINTS to arm a fault drill at startup)."""
    endpoint = endpoint or f"dynotpu_test_{uuid.uuid4().hex[:12]}"
    cmd = [
        str(bin_dir / "dynologd"),
        "--port=0",
        "--enable_ipc_monitor",
        f"--ipc_endpoint_name={endpoint}",
        f"--kernel_monitor_reporting_interval_s={kernel_interval_s}",
        "--nouse_JSON",
        *extra_flags,
    ]
    proc = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env={**os.environ, **env} if env else None,
    )
    port = None
    prom_port = None
    relay_port = None
    want_prom = any("--prometheus_port" in f for f in extra_flags)
    want_relay = "--relay" in extra_flags
    deadline = time.time() + 10
    # select-bounded raw-fd reads (readline() could block forever if the
    # daemon never prints the expected announcements; a buffered TextIO
    # would hide pending lines from select).
    fd = proc.stdout.fileno()
    pending = ""
    done = False
    while not done and time.time() < deadline:
        ready, _, _ = select.select([fd], [], [], max(0.0, deadline - time.time()))
        if not ready:
            break
        chunk = os.read(fd, 4096).decode(errors="replace")
        if not chunk:  # EOF: daemon exited
            break
        pending += chunk
        lines = pending.split("\n")
        pending = lines.pop()  # partial last line stays buffered
        for line in lines:
            if line.startswith("DYNOLOG_PORT="):
                port = int(line.split("=", 1)[1])
            elif line.startswith("DYNOLOG_PROMETHEUS_PORT="):
                prom_port = int(line.split("=", 1)[1])
            elif line.startswith("DYNOLOG_RELAY_PORT="):
                relay_port = int(line.split("=", 1)[1])
            if port is not None and (prom_port is not None or not want_prom) \
                    and (relay_port is not None or not want_relay):
                done = True
    if port is None or (want_prom and prom_port is None) \
            or (want_relay and relay_port is None):
        proc.kill()
        raise RuntimeError(
            "daemon did not announce its port"
            + (" (prometheus/relay port missing)" if port is not None else "")
        )
    return Daemon(proc, port, endpoint, prometheus_port=prom_port,
                  relay_port=relay_port)


def stop_daemon(daemon: Daemon) -> None:
    daemon.proc.terminate()
    try:
        daemon.proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        daemon.proc.kill()


def run_dyno(bin_dir, port: int, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [str(bin_dir / "dyno"), "--hostname=localhost", f"--port={port}", *args],
        capture_output=True,
        text=True,
        timeout=30,
    )


def write_snapshot(path, duty_pct) -> None:
    """Atomic write of a one-device FileTpuBackend snapshot whose
    tpu_duty_cycle_pct tests steer to trip (or arm) threshold rules."""
    snap = {
        "devices": [
            {
                "device": 0,
                "chip_type": "tpu_v5e",
                "metrics": {"tpu_duty_cycle_pct": duty_pct},
            }
        ]
    }
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        f.write(json.dumps(snap))
    os.replace(tmp, path)

"""The hand-off waits for nothing: dynologd's IPC thread blocks in
poll(2) on the fabric's socket and on the config manager's wake
descriptor, so a request is answered and a posted config kicked at the
thread's wake-up, not at the next pass of a 10 ms sleep loop (which cost
a request 5 ms at random phase, and a whole tick when it followed a
kick). Real dynologd, no JAX. Medians of 20 against limits well under
the old loop's, so a loaded CPU cannot flip them; the old loop cannot
pass them."""

from __future__ import annotations

import os
import pathlib
import random
import select
import statistics
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

from daemon_utils import start_daemon, stop_daemon  # noqa: E402
from dynolog_tpu.client import ipc  # noqa: E402

EXCHANGES = 20
LIMIT_MS = 3.0
JOB = 2626


@pytest.fixture()
def daemon(bin_dir):
    d = start_daemon(bin_dir)
    yield d
    stop_daemon(d)


@pytest.fixture()
def client(daemon):
    c = ipc.IpcClient()
    try:
        # The first request registers this process with the job.
        assert c.request_config(
            JOB, [os.getpid()], dest=daemon.endpoint) == ""
        yield c
    finally:
        c.close()


def _post(daemon, n: int) -> dict:
    return daemon.rpc({
        "fn": "setKinetOnDemandRequest",
        "config": f"ACTIVITIES_DURATION_MSECS={n}",
        "job_id": JOB,
        "pids": [0],
        "process_limit": 3,
    })


def _wakeups(daemon) -> dict:
    doc = daemon.rpc({"fn": "selftrace"})
    assert doc["status"] == "ok"
    return doc["ipc_wakeups"]


def test_request_is_answered_at_wakeup_not_at_a_tick(daemon, client):
    rng = random.Random(26)
    took_ms = []
    for _ in range(EXCHANGES):
        # Any phase of what used to be the 10 ms loop.
        time.sleep(rng.uniform(0.0, 0.012))
        t0 = time.perf_counter()
        reply = client.request_config(
            JOB, [os.getpid()], dest=daemon.endpoint)
        took_ms.append((time.perf_counter() - t0) * 1e3)
        assert reply == ""
    assert statistics.median(took_ms) < LIMIT_MS, sorted(took_ms)


def test_posted_config_is_kicked_then_fetched_at_wakeup(daemon, client):
    assert client.subscribe_kicks(JOB, dest=daemon.endpoint)
    # "sub" is fire-and-forget: a request behind it on the same socket
    # pair says the daemon has handled it.
    assert client.request_config(
        JOB, [os.getpid()], dest=daemon.endpoint) == ""
    kick_ms, fetch_ms = [], []
    for n in range(EXCHANGES):
        time.sleep(0.003 + 0.0007 * n)  # walk over the old loop's phases
        t0 = time.perf_counter()
        response = _post(daemon, n + 1)
        ready, _, _ = select.select([client.kick_sock], [], [], 2.0)
        t1 = time.perf_counter()
        assert response["activityProfilersTriggered"], response
        assert ready, "no kick within 2 s"
        assert client.wait_for_kick(0)  # drains it
        # The shim's next move, and the old loop's worst case: the thread
        # had just sent the kick and gone to sleep for a whole tick.
        config = client.request_config(
            JOB, [os.getpid()], dest=daemon.endpoint)
        t2 = time.perf_counter()
        assert f"ACTIVITIES_DURATION_MSECS={n + 1}" in config
        kick_ms.append((t1 - t0) * 1e3)  # RPC round trip included
        fetch_ms.append((t2 - t1) * 1e3)
    assert statistics.median(kick_ms) < LIMIT_MS, sorted(kick_ms)
    assert statistics.median(fetch_ms) < LIMIT_MS, sorted(fetch_ms)


def test_idle_ipc_thread_wakes_a_handful_of_times_a_second(daemon):
    time.sleep(0.3)  # start-up is over
    before = _wakeups(daemon)
    time.sleep(1.0)
    after = _wakeups(daemon)
    delta = {k: after[k] - before[k] for k in after}
    # Nobody talks to it: the poll's 250 ms timeout is the only cause,
    # where the sleep loop turned 100 times.
    assert delta["message"] == 0 and delta["posted"] == 0, delta
    assert 1 <= delta["timeout"] <= 15, delta


def test_each_wake_cause_is_counted_when_provoked(daemon, client):
    before = _wakeups(daemon)
    assert set(before) == {"message", "posted", "timeout"}
    # message: datagrams spaced so that each finds the thread blocked.
    for _ in range(3):
        time.sleep(0.02)
        assert client.request_config(
            JOB, [os.getpid()], dest=daemon.endpoint) == ""
    after_messages = _wakeups(daemon)
    assert after_messages["message"] - before["message"] >= 3
    assert after_messages["posted"] == before["posted"]
    # posted: one config for the registered process.
    assert _post(daemon, 5)["activityProfilersTriggered"]
    deadline = time.monotonic() + 2
    while (_wakeups(daemon)["posted"] == before["posted"]
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert _wakeups(daemon)["posted"] == before["posted"] + 1
    # timeout: silence.
    quiet = _wakeups(daemon)
    time.sleep(0.6)
    assert _wakeups(daemon)["timeout"] > quiet["timeout"]


def test_sigterm_reaches_a_daemon_blocked_in_poll(bin_dir):
    # Nothing is queued, so the IPC thread is inside its 250 ms poll when
    # the signal lands; stop() wakes it through the descriptor.
    d = start_daemon(bin_dir)
    time.sleep(0.3)
    t0 = time.monotonic()
    d.proc.terminate()
    assert d.proc.wait(timeout=10) == 0
    assert time.monotonic() - t0 < 5

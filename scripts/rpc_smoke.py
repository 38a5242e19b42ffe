#!/usr/bin/env python
"""CI control-plane smoke: the native framed RPC client must complete
one-shot and persistent round trips (and survive a peer-side idle close)
against a pure-Python reference peer, inside a wall-clock budget.

Pre-build by design (no C++, no jax): it pins the Python side of the
int32-length-prefixed wire protocol — framing, connection reuse, the
reconnect-once retry, and deadline-bounded failure — so a cluster-plane
regression (unitrace polling) fails CI in seconds, not on the next
machine with a chip. The daemon side of the same
protocol is covered by src/tests/RpcTest.cpp and
tests/test_rpc_eventloop.py once the tree is built.

Usage: python scripts/rpc_smoke.py [--budget-s=N]
Exit 0 on success; 1 with a reason on any failure.
"""

import json
import pathlib
import socket
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu.cluster.rpc import FRAME_HEADER, FramedRpcClient  # noqa: E402

DEFAULT_BUDGET_S = 20.0
ROUND_TRIPS = 50


def serve(lsock: socket.socket, close_after: int) -> None:
    """Reference peer: framed echo, closing each connection after
    `close_after` requests (0 = never) to exercise the client's retry."""
    while True:
        try:
            conn, _ = lsock.accept()
        except OSError:
            return

        def handle(conn=conn):
            served = 0
            conn.settimeout(5.0)
            with conn:
                while True:
                    try:
                        header = b""
                        while len(header) < FRAME_HEADER.size:
                            chunk = conn.recv(FRAME_HEADER.size - len(header))
                            if not chunk:
                                return
                            header += chunk
                        (length,) = FRAME_HEADER.unpack(header)
                        body = b""
                        while len(body) < length:
                            chunk = conn.recv(length - len(body))
                            if not chunk:
                                return
                            body += chunk
                        served += 1
                        reply = json.dumps(
                            {"echo": json.loads(body.decode()),
                             "served": served}).encode()
                        conn.sendall(FRAME_HEADER.pack(len(reply)) + reply)
                        if close_after and served >= close_after:
                            return
                    except OSError:
                        return

        threading.Thread(target=handle, daemon=True).start()


def main(argv: list[str]) -> int:
    budget_s = DEFAULT_BUDGET_S
    for a in argv[1:]:
        if a.startswith("--budget-s="):
            budget_s = float(a.split("=", 1)[1])
    t0 = time.perf_counter()

    lsock = socket.socket()
    lsock.settimeout(5.0)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(16)
    port = lsock.getsockname()[1]
    threading.Thread(
        target=serve, args=(lsock, 0), daemon=True).start()

    # Persistent: every round trip on ONE connection, counter monotonic.
    with FramedRpcClient("127.0.0.1", port, timeout_s=5.0) as client:
        for i in range(1, ROUND_TRIPS + 1):
            response = client.call({"fn": "getStatus", "i": i})
            if response is None or response.get("served") != i:
                print(f"FAIL: persistent round trip {i} broke "
                      f"(got {response})", file=sys.stderr)
                return 1

    # One-shot: a fresh connection per call still works (the wire format
    # has no session state).
    for i in range(5):
        with FramedRpcClient("127.0.0.1", port, timeout_s=5.0) as client:
            response = client.call({"oneshot": i})
            if response is None or response.get("served") != 1:
                print(f"FAIL: one-shot round trip {i} broke", file=sys.stderr)
                return 1
    lsock.close()

    # Idle-close retry: a peer that closes after each response (the
    # daemon's idle reaper, compressed) must be survived transparently.
    lsock2 = socket.socket()
    lsock2.settimeout(5.0)
    lsock2.bind(("127.0.0.1", 0))
    lsock2.listen(16)
    threading.Thread(
        target=serve, args=(lsock2, 1), daemon=True).start()
    with FramedRpcClient(
            "127.0.0.1", lsock2.getsockname()[1], timeout_s=5.0) as client:
        for i in range(3):
            response = client.call({"i": i})
            if response is None:
                print(f"FAIL: idle-close retry {i} not survived",
                      file=sys.stderr)
                return 1
    lsock2.close()

    elapsed = time.perf_counter() - t0
    if elapsed > budget_s:
        print(f"FAIL: smoke took {elapsed:.1f}s (budget {budget_s}s)",
              file=sys.stderr)
        return 1
    print(f"OK: {ROUND_TRIPS} persistent + 5 one-shot + 3 idle-close "
          f"round trips in {elapsed:.2f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

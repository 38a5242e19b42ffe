"""Of all the exits of the IPC thread's `poll(2)` since the daemon started
(`ipc_wakeups` in the reply of `dyno selftrace`: `message`, `posted`,
`timeout`), the share that were the 250 ms timeout: the thread woke for
nothing. Steady cells report step_ms_p50 alone, so that is what it moves."""

NAME = "ipc_timeout_wakeup_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "IPC hand-off"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    wakeups = (run.get("selftrace") or {}).get("ipc_wakeups")
    total = sum(wakeups.values()) if wakeups else 0
    return 100.0 * wakeups["timeout"] / total if total else None

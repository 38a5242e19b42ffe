"""1 - (union of op intervals) / span on the device planes, by the benchmark's
reducer: over all of the run's captures under capture traffic, from the
harness's own three-step trace under steady traffic."""

NAME = "device_idle_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    trace = run.get("trace")
    return trace["idle_pct"] if trace else None

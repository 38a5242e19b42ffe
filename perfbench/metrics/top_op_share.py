"""The largest op group's share of device op time, by the benchmark's reducer."""

NAME = "top_op_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace["groups"]:
        return None
    return 100.0 * trace["groups"][0][1] / trace["op_total_s"]

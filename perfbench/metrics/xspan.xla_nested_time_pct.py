"""Of the device planes of the capture the breakdown reads: (the sum of the
"XLA Ops" events' durations - the union of their intervals) over the sum, by
the benchmark's reducer: the time a reducer that adds durations counts
twice because an event lies inside another (a `while` over its body, three
times for a loop in a loop), and so how far `top_op_share` and the other
shares of this cell are off; it grows with the events a loop's trips put
into a capture. 0.0 where no event lies inside another."""

import xplane

NAME = "xspan.xla_nested_time_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    profile = xplane.load(trace["path"])
    summed = union = 0.0
    for i in range(run["device"]["count"]):
        plane = xplane.reduce_plane(
            xplane.find_plane(profile, xplane.device_plane_name(i)))
        if plane is not None:
            summed += sum(ns for ns, _count in plane.ops.values())
            union += plane.busy_ns
    if not summed:
        return None
    # starts are float64 nanoseconds: abutting events may differ in the
    # last bit, which is not nesting
    return round(max(0.0, 100.0 * (summed - union) / summed), 6)

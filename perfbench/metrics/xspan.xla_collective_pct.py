"""Of the device planes of the capture the breakdown reads: the time of the
collective ops on "XLA Ops" (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute, send, recv: the op names `dynolog_tpu.diagnose`
classes as `collective`) over all op time, by the benchmark's reducer. One
chip runs none, and reads 0.0 because its plane was summed, not by default."""

import xplane

NAME = "xspan.xla_collective_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)
TOKENS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
          "collective", "send", "recv")


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    profile = xplane.load(trace["path"])
    collective = total = 0.0
    for i in range(run["device"]["count"]):
        plane = xplane.reduce_plane(
            xplane.find_plane(profile, xplane.device_plane_name(i)))
        for op, (ns, _count) in (plane.ops if plane else {}).items():
            total += ns
            if any(token in op.lower() for token in TOKENS):
                collective += ns
    return 100.0 * collective / total if total else None

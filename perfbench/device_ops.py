"""What the readers of a capture's device ops share: op name -> nanoseconds,
summed over the device planes of the capture the breakdown reads, by the
benchmark's reducer (`xplane.reduce_plane`, the "XLA Ops" line).

A name is compared with `_` read as `-`: XLA names an op it inserts itself
after its opcode (`all-reduce.3`, `ragged-dot-none.7`) and one the program
wrote after the JAX primitive (`ragged_all_to_all.85`, `psum.1`).
"""

from __future__ import annotations

import functools

import xplane


@functools.lru_cache(maxsize=2)
def _ops(path: str, devices: int) -> dict:
    profile = xplane.load(path)
    out: dict = {}
    for i in range(devices):
        plane = xplane.reduce_plane(
            xplane.find_plane(profile, xplane.device_plane_name(i)))
        for op, (ns, _count) in (plane.ops if plane else {}).items():
            name = op.lower().replace("_", "-")
            out[name] = out.get(name, 0.0) + ns
    return out


def device_ops(run: dict) -> dict | None:
    """None where the run kept no trace."""
    trace = run.get("trace")
    if not trace:
        return None
    return _ops(trace["path"], run["device"]["count"])


def share_pct(run: dict, fragment: str) -> float | None:
    """Time of the ops whose name holds `fragment` over all op time, %."""
    ops = device_ops(run)
    total = sum(ops.values()) if ops else 0.0
    if not total:
        return None
    return 100.0 * sum(ns for op, ns in ops.items() if fragment in op) / total

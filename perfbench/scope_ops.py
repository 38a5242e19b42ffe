"""What the readers of a capture's scopes share: nanoseconds of device op
time under a `jax.named_scope` of the observed job, over the device planes
of the capture the breakdown reads.

An op's scope is in its metadata, not its name: the stat `tf_op` of the
op's XEventMetadata holds the path the framework gave it
("jit(step)/transpose(jvp(mla.attend))/pallas_call:"). `ProfileData` hands
out an event's own stats only, so the artifact is read through the protobuf
binding of the installed wheel (`xplane_pb2`, loaded by path, without the
package round it). Nothing of dynolog_tpu.trace is imported: the product
reads the same stat by its own wire walk, and its `scopes` table is held
against the plain reading of a synthetic XSpace in tests/test_deepseek_v2.py.

A time is an op's SELF time: an event's duration less the events directly
inside it on the same line (a `while` over its body), so that the scopes'
times add up to the time the device was busy.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import re

import xplane

WHEELS = (("tensorflow", "tsl/profiler/protobuf/xplane_pb2.py"),
          ("xprof", "protobuf/xplane_pb2.py"),
          ("tensorboard_plugin_profile", "protobuf/xplane_pb2.py"))
# the names in a path: "transpose(jvp(mla.attend))" holds three
NAME = re.compile(r"[A-Za-z_][\w.\-]*")


@functools.lru_cache(maxsize=1)
def binding():
    """The wheel's generated xplane_pb2 module; None where no wheel here
    ships one."""
    for package, rel in WHEELS:
        try:
            spec = importlib.util.find_spec(package)
        except (ImportError, ValueError):
            continue
        for root in (spec.submodule_search_locations or ()) if spec else ():
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                continue
            try:
                module_spec = importlib.util.spec_from_file_location(
                    "perfbench_xplane_pb2", path)
                module = importlib.util.module_from_spec(module_spec)
                module_spec.loader.exec_module(module)
                return module
            except Exception:  # noqa: BLE001 - a wheel protobuf cannot load: the next
                continue
    return None


def self_times(events: list) -> list:
    """[(metadata id, self ps)] of (metadata id, offset ps, duration ps)
    events of one line."""
    out = []
    open_events: list = []  # [end, index into out], innermost last
    for meta, offset, duration in sorted(
            events, key=lambda e: (e[1], -e[2])):
        while open_events and open_events[-1][0] <= offset:
            open_events.pop()
        end = offset + duration
        if open_events and end <= open_events[-1][0]:
            holder = open_events[-1][1]
            out[holder] = (out[holder][0], out[holder][1] - duration)
        open_events.append((end, len(out)))
        out.append((meta, duration))
    return out


@functools.lru_cache(maxsize=2)
def _paths(path: str, devices: int) -> tuple | None:
    """((the names in an op's path, self ns), ...) over the device planes."""
    pb2 = binding()
    if pb2 is None:
        return None
    with open(path, "rb") as f:
        space = pb2.XSpace.FromString(f.read())
    wanted = {xplane.device_plane_name(i) for i in range(devices)}
    out = []
    for plane in space.planes:
        if plane.name not in wanted:
            continue
        stat_ids = {k for k, v in plane.stat_metadata.items()
                    if v.name == "tf_op"}
        names: dict = {}  # metadata id -> the names in its path
        for line in plane.lines:
            if line.name != xplane.XLA_OPS:
                continue
            events = [(e.metadata_id, e.offset_ps, e.duration_ps)
                      for e in line.events]
            for meta, self_ps in self_times(events):
                if meta not in names:
                    text = next(
                        (s.str_value for s in plane.event_metadata[meta].stats
                         if s.metadata_id in stat_ids), "")
                    names[meta] = tuple(NAME.findall(text.split(";", 1)[0]))
                out.append((names[meta], self_ps / 1e3))
    return tuple(out)


def scope_share_pct(run: dict, prefix: str) -> float | None:
    """Self time of the ops whose path holds a name that starts with
    `prefix` over all op time, %; None where the run kept no trace, no wheel
    reads it or the planes hold no op."""
    trace = run.get("trace")
    rows = _paths(trace["path"], run["device"]["count"]) if trace else None
    total = sum(ns for _, ns in rows) if rows else 0.0
    if not total:
        return None
    under = sum(ns for names, ns in rows
                if any(name.startswith(prefix) for name in names))
    return 100.0 * under / total

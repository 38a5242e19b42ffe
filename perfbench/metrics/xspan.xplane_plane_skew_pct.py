"""Median over a run's captures of (largest - smallest device plane) / largest,
from the manifest's `planes` (bytes of each `/device:TPU:<i>` plane of the
XSpace): whether four planes are one plane four times or uneven, which decides
how a drain could be divided. 0.0 where there is one device plane."""

import re

import stats

NAME = "xspan.xplane_plane_skew_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")


def read(run: dict):
    skews = []
    for capture in run["captures"]:
        rows = capture["manifest"].get("planes") if capture["ok"] else None
        sizes = [row["bytes"] for row in rows or []
                 if DEVICE_PLANE.fullmatch(row["name"])]
        if sizes and max(sizes) > 0:
            skews.append(100.0 * (max(sizes) - min(sizes)) / max(sizes))
    return stats.median(skews) if skews else None

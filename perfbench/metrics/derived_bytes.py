"""Median over the window's captures of the bytes the export child wrote
beside the artifact: `<host>.summary.json` + `<host>.trace.json.gz`."""

import stats

NAME = "derived_bytes"
UNIT = "bytes"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    sizes = [sum(f["bytes"] for ext, f in c["derived"].items() if ext != "tmp")
             for c in run["captures"] if "derived_ms" in c]
    return stats.median(sizes) if sizes else None

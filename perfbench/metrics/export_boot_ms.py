"""Median length of the manifests' `export.boot` span: from just before the
export child's `Popen`, as its capture's window opens, to the unix time the
child stamped on `ready` (fork and exec, the interpreter at nice 19, every
import). Its margin below window + drain is what keeps `export_warm_pct` at
100. Over the window's ok captures whose hand-over was warm."""

import conversions

NAME = "export_boot_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.life_median_ms(run, conversions.BOOT)

"""From a capture's manifest (its mtime) to the start of that capture's
`trace.convert` span, matched by the request's trace id: the export child's
spawn, its interpreter and its imports. Median over the window's captures
whose span the journal holds."""

import selftrace
import stats

NAME = "convert_lag_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    begun = selftrace.convert_starts_us(run)
    if not begun:
        return None
    lags = [begun[trace_id] / 1e3 - c["done_t"] * 1e3
            for c in run["captures"] if c["ok"]
            and (trace_id := c["manifest"].get("trace_ctx", "").split("/")[0])
            in begun]
    return stats.median(lags) if lags else None

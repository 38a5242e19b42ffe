"""The larger, over the capture's two `dynolog.clock_sync` marks, of |event start
mapped to unix time - the `unix_ns` the mark carries|: how far the overlay of
spans on the device trace can be trusted. 1e6 where the trace holds no mark."""

import spans

NAME = "xspan.trace_clock_skew_us"
UNIT = "us"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    over = spans.overlay(run)
    return over["skew_us"] if over else None

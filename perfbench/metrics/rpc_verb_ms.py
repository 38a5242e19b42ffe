"""Median length of the daemon's `rpc.setKinetOnDemandRequest` spans that
began inside the window: the body of the verb `dyno gputrace` sends, the part
of `pickup_ms` that is the daemon's RPC thread."""

import selftrace

NAME = "rpc_verb_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "IPC hand-off"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return selftrace.window_median_ms(run, selftrace.CAPTURE_VERB)

"""Of the device planes of the capture the breakdown reads: the time of the ops
on "XLA Ops" whose name holds `all-to-all` (`_` read as `-`: the expert layer's
exchange is `ragged_all_to_all.<n>` on the TPU) over all op time, by the
benchmark's reducer: the expert exchange's part of what
`xspan.xla_collective_pct` is meant to hold. A capture that holds no such op
(one chip; a dense job over a mesh) reads 0.0 because its planes were summed,
not by default."""

import device_ops

NAME = "xspan.xla_all_to_all_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return device_ops.share_pct(run, "all-to-all")

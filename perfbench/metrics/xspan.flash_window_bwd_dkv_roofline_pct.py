"""Of the device planes of the capture the breakdown reads: the share of its
roofline that the WINDOWED flash-attention dk and dv kernel
(`flash_attention_window_bwd_dkv`, `dynolog_tpu/ops/flash_attention.py` under a
`window`: a job's `sliding_attention` layers) reaches: the least time the
chip could take for a call's useful work inside the band (operations and
bytes from the job's shapes, `perfbench/window_costs.py`: the stated head
width, k and v at the key/value heads; peaks from `perfbench/peaks.json`;
the larger of the two bounds) over the kernel's traced time, all its events
of the capture together. 0.0 where the capture holds no event of the kernel:
a job without a windowed layer, or a program older than the window."""

import window_costs

NAME = "xspan.flash_window_bwd_dkv_roofline_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return window_costs.roofline_pct(run, "flash_attention_window_bwd_dkv")

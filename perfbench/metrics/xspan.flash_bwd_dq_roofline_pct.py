"""Of the device planes of the capture the breakdown reads: the share of its
roofline that the flash-attention dq kernel (`flash_attention_bwd_dq`,
`dynolog_tpu/ops/flash_attention.py`) reaches: the least time the chip could
take for a call's useful causal work (operations and bytes from the job's
shapes, `perfbench/kernel_costs.py`; peaks from `perfbench/peaks.json`; the
larger of the two bounds) over the kernel's traced time, all its events of
the capture together. 0.0 where the capture holds no event of the kernel."""

import kernel_costs

NAME = "xspan.flash_bwd_dq_roofline_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return kernel_costs.roofline_pct(run, "flash_attention_bwd_dq")

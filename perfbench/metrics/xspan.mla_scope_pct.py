"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the job's `mla.` scopes (`jax.named_scope` in
`dynolog_tpu/models/mla.py`: `mla.project`, `mla.latent`, `mla.expand`,
`mla.attend`, the flash kernels among its ops, and `mla.out`; read from each
op's `tf_op` through the wheel's protobuf binding, `scope_ops.py`) over all
op time: how much of a step is latent attention. 0.0 for a job without it,
because its planes were summed, not by default."""

import scope_ops

NAME = "xspan.mla_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "mla.")

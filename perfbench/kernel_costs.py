"""The operations and bytes the three flash-attention kernels need
(`dynolog_tpu/ops/flash_attention.py`: `flash_attention_fwd`,
`flash_attention_bwd_dq`, `flash_attention_bwd_dkv`), computed from the
job's shapes, and a kernel's share of its roofline from a capture.

Useful causal work only: a query sees the S^2 / 2 keys at or before it (the
kernels also compute the masked half of the blocks on the diagonal; that is
their cost, not the algorithm's). A head of one sequence, with d_qk the
width of queries and keys and d_v of values:

    forward   2 (S^2/2) (d_qk + d_v)          Q K^T and P V
    dq        2 (S^2/2) (2 d_qk + d_v)        Q K^T again, dO V^T, dS K
    dkv       2 (S^2/2) (2 d_qk + 2 d_v)      Q K^T again, P^T dO, dO V^T,
                                              dS^T Q

q, k, v, o and their gradients are read or written once, in the job's type:
the forward reads q, k, v and writes o; dq reads q, k, v, dO and writes dq;
dkv reads q, k, v, dO and writes dk, dv. (The logsumexp and delta rows, four
bytes a query, are left out: under a hundredth of the rest.)

One event of a kernel on one device's op line is one layer's call over that
device's share of batch x heads: the mesh divides both evenly
(`transformer._softmax_attention`'s shard_map), so an event's work is the
global work of a call over the devices. A layer's kernels all have the
job's one shape (a hybrid job calls them in its full-attention layers only).

The share is the least time the chip could take (the larger of operations
over the peak rate and bytes over the peak bandwidth, perfbench/peaks.json)
over the kernel's traced time. The kernels take their operands to float32
before the products, so against the bfloat16 peak the share says how far a
kernel is from what the chip could do, not from what float32 could.
"""

from __future__ import annotations

import functools

import cells
import xplane

# the name's fragment -> ((d_qk, d_v) multipliers of a head's S^2 operations,
# the tensors of width (d_qk, d_v) a token a head that it reads or writes)
KERNELS = {
    "flash_attention_fwd": ((1, 1), (2, 2)),      # q k | v o
    "flash_attention_bwd_dq": ((2, 1), (3, 2)),   # q k dq | v dO
    "flash_attention_bwd_dkv": ((2, 2), (3, 3)),  # q k dk | v dO dv
}
TYPE_BYTES = {"bfloat16": 2, "float32": 4}


def head_widths(job: dict) -> tuple:
    """(d_qk, d_v) of the job's attention."""
    if job.get("attn_type") == "mla":
        return (job["qk_nope_head_dim"] + job["qk_rope_head_dim"],
                job["v_head_dim"])
    head = job["d_model"] // job["n_heads"]
    return head, head


def call_cost(job: dict, kernel: str) -> tuple:
    """(operations, bytes) of one call of `kernel` over the job's whole
    batch and all its heads."""
    d_qk, d_v = head_widths(job)
    heads = job["batch"] * job["n_heads"]
    seq = job["seq"]
    (m_qk, m_v), (t_qk, t_v) = KERNELS[kernel]
    flops = heads * 2 * (seq * seq / 2) * (m_qk * d_qk + m_v * d_v)
    nbytes = (heads * seq * (t_qk * d_qk + t_v * d_v)
              * TYPE_BYTES[job["dtype"]])
    return flops, nbytes


@functools.lru_cache(maxsize=2)
def _ops(path: str, devices: int) -> dict:
    """op name -> (nanoseconds, events), summed over the device planes."""
    profile = xplane.load(path)
    out: dict = {}
    for i in range(devices):
        plane = xplane.reduce_plane(
            xplane.find_plane(profile, xplane.device_plane_name(i)))
        for op, (ns, count) in (plane.ops if plane else {}).items():
            had = out.get(op, (0.0, 0))
            out[op] = (had[0] + ns, had[1] + count)
    return out


def kernel_events(run: dict, kernel: str) -> tuple | None:
    """(nanoseconds, events) of the ops whose name holds `kernel`, over the
    device planes of the capture the breakdown reads (one reduction a
    capture, whichever of the three readers asks first); None where the run
    kept no trace."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [row for op, row in _ops(
        trace["path"], run["device"]["count"]).items()
        if kernel in op.replace("-", "_")]
    return sum(ns for ns, _ in found), sum(count for _, count in found)


def roofline_pct(run: dict, kernel: str) -> float | None:
    """The kernel's share of its roofline, %: 0.0 where the capture holds
    no event of it (the kernel is not on the job's path), None where the
    run kept no trace."""
    found = kernel_events(run, kernel)
    if found is None:
        return None
    ns, count = found
    if not count or not ns:
        return 0.0
    try:
        cell = cells.load_cell(run["workload"])
        peaks = cells.load_peaks(run["device"]["kind"])
        flops, nbytes = call_cost(cell.job, kernel)
    except (cells.BenchmarkError, KeyError):
        return None  # a run of no cell of the benchmark: nothing to hold it to
    devices = run["device"]["count"]
    least_s = max(flops / devices / peaks["bf16_flops_per_s"],
                  nbytes / devices / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * count / (ns / 1e9)

"""The operations and bytes the three WINDOWED flash-attention kernels need
(`dynolog_tpu/ops/flash_attention.py` under a `window`:
`flash_attention_window_fwd`, `flash_attention_window_bwd_dq`,
`flash_attention_window_bwd_dkv`), computed from the job's shapes, and a
kernel's share of its roofline from a capture. `kernel_costs.py` is the plain
causal kernels' file and is held (PERF.md Open 33: it knows no band, no
stated head width and no grouped heads); this one knows all three and reads
the windowed kernels alone.

Useful work only: a query at position i sees the keys j with
0 <= i - j < W, so a head of one sequence of S positions has

    pairs = W (W + 1) / 2 + (S - W) W        (W taken as min(W, S))

visible (query, key) pairs: the first W queries see 1, 2, ... W keys, every
later one W. The kernels also compute the masked part of the tiles the band's
two edges cross; that is their cost, not the algorithm's, so no share can
read over 100 %. With d the head's width (`attn_head_dim`, else d_model /
n_heads; keys and values alike), the products' multipliers as
`kernel_costs.KERNELS` has them:

    forward   2 pairs (d + d)            Q K^T and P V
    dq        2 pairs (2 d + d)          Q K^T again, dO V^T, dS K
    dkv       2 pairs (2 d + 2 d)        Q K^T again, P^T dO, dO V^T, dS^T Q

over batch x n_heads QUERY heads. Bytes, each tensor read or written once in
the job's type: q, o, dq, dO at n_heads heads, k, v, dk, dv at n_kv_heads
(the grouped kernels fetch a key/value head once a group and write dk and dv
once a key/value head): the forward reads q, k, v and writes o; dq reads q,
k, v, dO and writes dq; dkv reads q, k, v, dO and writes dk, dv.

One event of a kernel on a device's op line is one layer's call. The share
is the least time the chip could take (the larger of operations over the
peak rate and bytes over the peak bandwidth, perfbench/peaks.json) over the
kernel's traced time, all its events of the capture together.
"""

from __future__ import annotations

import cells
import kernel_costs

# the name's fragment -> (the multiplier of 2 pairs d operations, the tensors
# a token it moves at the query heads, at the key/value heads)
KERNELS = {
    "flash_attention_window_fwd": (sum(kernel_costs.KERNELS[
        "flash_attention_fwd"][0]), 2, 2),          # q o | k v
    "flash_attention_window_bwd_dq": (sum(kernel_costs.KERNELS[
        "flash_attention_bwd_dq"][0]), 3, 2),       # q dO dq | k v
    "flash_attention_window_bwd_dkv": (sum(kernel_costs.KERNELS[
        "flash_attention_bwd_dkv"][0]), 2, 4),      # q dO | k v dk dv
}


def visible_pairs(seq: int, window: int) -> float:
    """The (query, key) pairs a head of one sequence sees."""
    w = min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def call_cost(job: dict, kernel: str) -> tuple:
    """(operations, bytes) of one call of `kernel` over the job's whole
    batch and all its heads."""
    width = job.get("attn_head_dim") or job["d_model"] // job["n_heads"]
    heads = job["n_heads"]
    kv_heads = job.get("n_kv_heads") or heads
    batch, seq = job["batch"], job["seq"]
    multiplier, at_heads, at_kv_heads = KERNELS[kernel]
    flops = (batch * heads * 2 * visible_pairs(seq, job["sliding_window"])
             * multiplier * width)
    nbytes = (batch * seq * width * (at_heads * heads + at_kv_heads * kv_heads)
              * kernel_costs.TYPE_BYTES[job["dtype"]])
    return flops, nbytes


def roofline_pct(run: dict, kernel: str) -> float | None:
    """The windowed kernel's share of its roofline, %: 0.0 where the capture
    holds no event of it (no layer of the job has a window; a program older
    than the window has no such kernel), None where the run kept no trace."""
    found = kernel_costs.kernel_events(run, kernel)
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

"""Share of its roofline that the Lloyd ``pallas_call`` reaches, in %.

The least time one call could take on this chip is the larger of its
operations over the peak FLOP/s and its bytes over the peak HBM bandwidth
(``trace_reduce.lloyd_kernel_cost``, ``peaks.json``); the share is that
time over the call's mean device time in the trace.  Which of the two
bounds it is printed on stderr.
"""
import sys

import numpy as np

import trace_reduce


def read(ctx):
    calls = ctx.kernel_calls()
    if not calls:
        return None
    cfg = ctx.cfg
    ops, nbytes = trace_reduce.lloyd_kernel_cost(
        cfg["slots"], cfg["n_max"], cfg["k_max"])
    pk = trace_reduce.peaks(ctx.device_kind)
    t_ops, t_bytes = ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    mean = float(np.mean(calls))
    print(f"kmeans_roofline: {len(calls)} calls, mean {mean} s, floor "
          f"{max(t_ops, t_bytes)} s ({'memory' if t_bytes >= t_ops else 'compute'}"
          f"-bound: {ops} ops, {nbytes} bytes)", file=sys.stderr)
    return 100.0 * max(t_ops, t_bytes) / mean

"""Kernel layer: the Pallas prefill attention kernel's share of its v5e
roofline, in percent: the least time its calls in the trace could take
at the chip's peaks (causal FLOPs of each call, or q, k, v and o moved
once, from the shapes in each call's HLO), over their summed device
time."""

import counts
import devtrace
from peaks import peaks


def read(run):
    t = run.trace
    if t is None:
        return None
    pk = peaks(run.device["kind"])
    least = spent = 0.0
    for op in t.kernel("flash_attention"):
        res, opd = devtrace.shapes(op.name)    # o; q, k, v
        b, h, s, d = res[0][1]
        kvh = opd[1][1][1]
        flops, nbytes = counts.flash_attention_cost(b, h, kvh, s, d)
        least += counts.roofline_s(flops, nbytes, pk)[0]
        spent += op.dur_ns * 1e-9
    return 100.0 * least / spent if spent > 0 else None

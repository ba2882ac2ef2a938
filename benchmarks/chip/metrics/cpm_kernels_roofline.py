"""Kernel layer: the CPM page kernels of the decode chunk (``gather_rows``,
``fused_stream``, ``scatter_rows``: the Pallas calls inside the
``jit_run`` programs, where no other kernel runs), their share of the
v5e roofline from the bytes each call needs to move (shapes from its
HLO), over their summed device time, in percent."""

import counts
import devtrace
from peaks import peaks

KERNELS = ("gather_rows", "scatter_rows", "fused_stream")


def call_bytes(op) -> float:
    res, opd = devtrace.shapes(op.name)
    if op.short == "gather_rows":              # (K,) idx -> (K, 1, N)
        _, (k, _, n) = res[0]
        return counts.gather_rows_bytes(k, n)
    if op.short == "scatter_rows":             # (R,) inverse map -> (R, 1, N)
        _, (r, _, n) = res[0]
        return counts.scatter_rows_bytes(r, r, n)
    # fused_stream (or any other): operands read once, results written
    return float(sum(devtrace.nbytes(x) for x in res + opd))


def read(run):
    t = run.trace
    if t is None:
        return None
    pk = peaks(run.device["kind"])
    least = spent = 0.0
    for op in t.ops:
        if op.module == "jit_run" and (op.short in KERNELS
                                       or "tpu_custom_call" in op.name):
            least += counts.roofline_s(0.0, call_bytes(op), pk)[0]
            spent += op.dur_ns * 1e-9
    return 100.0 * least / spent if spent > 0 else None

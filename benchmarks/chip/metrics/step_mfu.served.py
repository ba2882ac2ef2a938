"""Model layer: useful model FLOPs of the tokens served in the traced
stretch, over (device busy seconds x the chip's peak bf16 FLOP/s), in
percent.  Busy time, not the window: at a fixed offered rate the FLOPs
per window are fixed."""

from peaks import peaks
from stats import served_flops


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or not run.win.trace_span:
        return None
    flops = served_flops(run, *run.win.trace_span)
    if flops <= 0:
        return None
    return 100.0 * flops / (t.busy_s * peaks(run.device["kind"])["flops"])

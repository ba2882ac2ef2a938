"""Model layer, prefill: useful FLOPs of the prefill programs that ran in
the trace over (their device time x the chip's peak bf16 FLOP/s), in
percent.  A prefill is a program execution that holds the prompt
attention kernel; its batch and prompt length are read from that
kernel's shapes."""

import devtrace
from peaks import peaks


def read(run):
    t = run.trace
    if t is None:
        return None
    flops = seconds = 0.0
    for m in t.modules:
        attn = [o for o in t.inside(m) if o.short == "flash_attention"]
        if not attn:
            continue
        b, _, s, _ = devtrace.shapes(attn[0].name)[0][0][1]
        flops += b * run.counts.prefill_flops(run.cfg, s)
        seconds += m.dur_ns * 1e-9
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * peaks(run.device["kind"])["flops"])

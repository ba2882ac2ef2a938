#!/usr/bin/env python3
"""The two readings that a cell's correctness limit is set between.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,...  [--control 3] [--seconds 10]

One process, one set-up.  For each seed the program gets fresh weights
from that seed and serves a short window of the cell's own traffic, and
the sample that ``run.py`` would compare goes to the float32 reference:
the widest gap of a served token below the reference's best is the
program's reading (the lower reading is their largest).  For the first
``--control`` seeds the same positions are also read for the control,
the reference computed in float8 (the step below the configuration's
bfloat16): the gap of the token the float8 forward puts first (the upper
reading is their smallest).  A JSON summary is the last line.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness              # noqa: E402
from traffic import schedule  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    _, cell, cfg, traffic = harness.load_cell(args.workload)
    if args.rehearse:
        cfg, traffic = harness.rehearsal_sizes(cfg, traffic)
    try:
        device = harness.start_jax(args.rehearse, cell["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    clock = harness.CompileClock()
    gw = harness.build_gateway(cfg, harness.make_params(cfg, seeds[0]))
    engine = gw.pool.engine
    harness.warm_up(gw, cfg, traffic, seeds[0])
    rows = []

    async def go():
        await harness.warm_stream(gw, cfg, traffic, seeds[0])
        for i, seed in enumerate(seeds):
            if engine.params is None:
                engine.params = harness.make_params(cfg, seed)
            t = time.perf_counter()
            reqs = schedule(traffic, seed, args.seconds, harness.vocab(cfg),
                            extra=0.25)
            win = await harness.serve_window(gw, reqs, args.seconds, clock)
            checks = harness.served_checks(win, harness.vocab(cfg))
            engine.params = None            # the reference needs the room
            gc.collect()
            sample = harness.sample_for_reference(win, seed)
            gaps = harness.logit_gaps(cfg, seed, sample,
                                      fp8_control=i < args.control)
            row = {"seed": seed, "program_gap_sd": max(g for g, _ in gaps),
                   "served_tokens": sum(r.budget for r in sample),
                   "requests": len(sample), **checks,
                   "programs_in_window": win.programs,
                   "wall_s": time.perf_counter() - t}
            if i < args.control:
                row["control_gap_sd"] = max(c for _, c in gaps)
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)

    asyncio.run(go())
    lower = max(r["program_gap_sd"] for r in rows)
    controls = [r["control_gap_sd"] for r in rows if "control_gap_sd" in r]
    print(json.dumps({"device": device, "workload": cell["name"],
                      "lower": lower,
                      "upper": min(controls) if controls else None,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

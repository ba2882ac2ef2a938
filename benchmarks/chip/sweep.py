#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates in one
process, after one set-up.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,4,6,8 \\
        [--seconds 20] [--seed 1]

For each rate, in the order given, the cell's traffic file is served at
that rate for ``--seconds`` and the window's requests are judged against
the file's limits (TTFT and TPOT).  The knee is the highest rate at which
the stated share of requests met both limits with no growing backlog:
the queue wait of the window's last third is no more than twice that of
its first third plus one tick.  One line per rate goes to standard
error, and a JSON list of the rows is the last line of standard output.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness              # noqa: E402
import stats                # noqa: E402
from traffic import schedule  # noqa: E402


def judge(win, limits: dict) -> dict:
    t0, recs = win.t0, win.measured
    ttft = [stats.ttft_s(r, t0) for r in recs]
    tpot = [stats.tpot_s(r) for r in recs]
    ok = sum(a * 1e3 <= limits["ttft_ms"] and b * 1e3 <= limits["tpot_ms"]
             for a, b in zip(ttft, tpot))
    waits = [r.admitted - (t0 + r.due) if not math.isnan(r.admitted)
             else math.inf for r in recs]
    third = max(1, len(waits) // 3)
    first = stats.percentile(waits[:third], 50)
    last = stats.percentile(waits[-third:], 50)
    out_tok = sum(1 for r in win.recs for t in r.times
                  if t0 <= t < win.t1)
    return {"requests": len(recs), "attained": ok / max(1, len(recs)),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p95_ms": 1e3 * stats.percentile(tpot, 95),
            "wait_first_third_ms": 1e3 * first,
            "wait_last_third_ms": 1e3 * last,
            "output_tok_s": out_tok / win.seconds,
            "programs_in_window": win.programs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated request rates, req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _, cell, cfg, traffic = harness.load_cell(args.workload)
    if args.rehearse:
        cfg, traffic = harness.rehearsal_sizes(cfg, traffic)
    try:
        device = harness.start_jax(args.rehearse, cell["chips"])
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    clock = harness.CompileClock()
    gw = harness.build_gateway(cfg, harness.make_params(cfg, args.seed))
    harness.warm_up(gw, cfg, traffic, args.seed)
    limits = traffic["limits"]
    rows = []

    async def sweep():
        await harness.warm_stream(gw, cfg, traffic, args.seed)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            at = dict(traffic, arrival=dict(traffic["arrival"],
                                            rate_rps=rate))
            reqs = schedule(at, args.seed + i, args.seconds,
                            cfg["vocab_size"], extra=0.25)
            t = time.perf_counter()
            win = await harness.serve_window(gw, reqs, args.seconds, clock)
            row = {"rate_rps": rate, **judge(win, limits),
                   "wall_s": time.perf_counter() - t}
            backlog = (row["wait_last_third_ms"]
                       > 2 * row["wait_first_third_ms"] + 200)
            row["meets"] = (row["attained"] >= limits["attainment"]
                            and not backlog)
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
            gw.collect_delivered()

    asyncio.run(sweep())
    knee = max((r["rate_rps"] for r in rows if r["meets"]), default=None)
    print(json.dumps({"device": device, "workload": cell["name"],
                      "knee_rps": knee, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

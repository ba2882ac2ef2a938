#!/usr/bin/env python3
"""One measured run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--rehearse]

The cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
file (``configs/``) under a traffic file (``traffic/``).  A run goes
through these phases in order:

1. device check: JAX's platform must be ``tpu`` with the chips the cell
   asks for; otherwise the run exits 3 and prints no result;
2. weights: drawn from ``--seed`` on the device in one jitted call;
3. gateway: the program's ``Engine`` and ``Gateway`` with the
   configuration's pool geometry;
4. warm-up: every (prompt length, bucket size) admission the traffic file
   lists, the decode chunk, streaming and retirement, all from the
   persistent compile cache in ``<checkout>/.jax_cache`` once it is warm;
5. window: ``--seconds`` of open-loop traffic through the Gateway's
   asyncio face, each request sent at its due time;
6. drain: the window's requests finish (load goes on meanwhile);
7. correctness: every request of the window is checked exactly, and a
   sample drawn from the seed is compared with the float32 reference
   (``reference/``) once the program's state is freed;
8. output: the last line of standard output is one JSON object.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a steady stretch of the window is taken
and the metrics are the cell's per-layer metrics.  Each metric is a
reader ``metrics/<name>.py`` found by its name.

``--rehearse`` runs the same phases at a tiny size on whatever JAX finds
(the CPU here); its result line carries no metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import asyncio      # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import pathlib      # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import types        # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness      # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; prints no metrics")
    return ap.parse_args(argv)


def wanted_metrics(bench: dict, cell: dict, trace: bool) -> list:
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_metrics(entries: list, run) -> dict:
    out = {}
    for m in entries:
        mod = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench, cell, cfg, traffic = harness.load_cell(args.workload)
    if args.rehearse:
        cfg, traffic = harness.rehearsal_sizes(cfg, traffic)
    try:
        device = harness.start_jax(args.rehearse, cell["chips"])
    except harness.NoChip as e:
        say(f"run: {e}")
        return 3
    import jax
    say(f"device: {device['kind']} x{device['count']} "
        f"(platform {device['platform']}); cell {cell['name']}; "
        f"seed {args.seed}; window {args.seconds} s; trace {args.trace}")

    clock = harness.CompileClock()
    t = time.perf_counter()
    params = harness.make_params(cfg, args.seed)
    say(f"weights: {sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f}"
        f"B float32 in {time.perf_counter() - t:.2f} s")
    gw = harness.build_gateway(cfg, params)
    del params
    t = time.perf_counter()
    harness.warm_up(gw, cfg, traffic, args.seed)

    from traffic import schedule
    reqs = schedule(traffic, args.seed, args.seconds, cfg["vocab_size"])
    trace_dir = None
    if args.trace:
        trace_dir = str(harness.ROOT / ".bench_traces"
                        / f"{cell['name']}.{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    async def served():
        await harness.warm_stream(gw, cfg, traffic, args.seed)
        say(f"warm-up: {time.perf_counter() - t:.2f} s, {clock.programs} "
            f"programs lowered, {clock.compile_s:.2f} s of backend compiles")
        setup_s = time.perf_counter() - T_START
        return setup_s, await harness.serve_window(
            gw, reqs, args.seconds, clock, trace_dir)

    setup_s, win = asyncio.run(served())
    peak = harness.device_peak_bytes()
    checks = harness.served_checks(win, cfg["vocab_size"])
    del gw
    harness.free()

    late = sorted(win.lateness)
    buckets: dict = {}                     # (tick, prompt length) -> seated
    for r in win.recs:
        if r.admit_tick >= 0:
            key = (r.admit_tick, len(r.prompt))
            buckets[key] = buckets.get(key, 0) + 1
    sizes = sorted(buckets.values())
    say(f"window: {len(win.measured)} requests due in {args.seconds} s, "
        f"{sum(r.done for r in win.measured)} finished; generator late "
        f"by median {1e3 * late[len(late) // 2]:.3f} ms, max "
        f"{1e3 * late[-1]:.3f} ms; {win.programs} programs lowered in "
        f"the window; admission buckets: {len(sizes)}, largest "
        f"{sizes[-1] if sizes else 0}; client errors: "
        f"{sum(bool(r.error) for r in win.measured)}")

    summary = None
    if args.trace:
        import devtrace
        summary = devtrace.summarize(trace_dir)

    # the float32 reference over a sample of what was served
    sample = harness.sample_for_reference(win, args.seed)
    t = time.perf_counter()
    gaps = [g for g, _ in harness.logit_gaps(cfg, args.seed, sample)]
    gap = max(gaps) if gaps else math.inf
    say(f"reference: {len(sample)} requests, "
        f"{sum(r.budget for r in sample)} served tokens, "
        f"{time.perf_counter() - t:.2f} s")
    # the limit on the widest logit gap (standard deviations of the
    # reference's logits) is the configuration's; PERF.md gives the
    # readings it was set from
    limit = cfg["logit_gap_limit_sd"]
    compared = {
        "logit_gap_sd": {"value": gap, "limit": limit},
        "unfinished": {"value": checks["unfinished"], "limit": 0},
        "stream_mismatch": {"value": checks["stream_mismatch"], "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    import counts
    # what a metric reader (``metrics/<name>.py``) sees of this run
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, device=device, traffic=traffic,
        seconds=args.seconds, setup_s=setup_s, win=win, trace=summary,
        counts=counts)
    metrics = {}
    if not args.rehearse:
        metrics = read_metrics(wanted_metrics(bench, cell, args.trace), run)
        informative = read_metrics(
            [{"name": n, "unit": u} for n, u in
             (("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"),
              ("output_tok_s", "tokens/s"), ("queue_wait_p95_ms", "ms"),
              ("occupancy.overload", "%"))], run)
        say("all readings (informative): " + json.dumps(informative))
    if peak is not None:
        device["memory_peak_bytes"] = int(peak)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": correct, "attempted": len(win.measured),
              "failed": checks["unfinished"], "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if args.rehearse:
        result["rehearsal"] = True
    result["checks"] = compared
    for name, c in compared.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

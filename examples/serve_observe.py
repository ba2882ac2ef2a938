"""Observability tour: trace, meter, and cycle-audit the serving gateway.

Replays the bursty trace from ``examples/serve_gateway.py`` with the
``repro.obs`` telemetry on (the default) and walks the three exports the
PR-9 subsystem adds (files land under the gitignored ``artifacts/``):

  * ``trace.json`` — Chrome/Perfetto ``trace_event`` spans for every
    serving layer (gateway tick, admission, prefill, decode chunk,
    park/restore), each carrying BOTH wall-clock time and the pool's
    virtual decode-step clock (``vstep``/``vdur`` in the args).  Open it
    at https://ui.perfetto.dev or ``chrome://tracing``.
  * ``metrics.prom`` — the process-global metrics registry in Prometheus
    text exposition (the same series backing ``Gateway.stats()``);
  * the **cycle-drift table** — per op family, the op table's predicted
    concurrent-step cycles next to jaxpr-measured scan trips of the
    reference lowering.  Zero drift means the lowering still matches the
    paper's budgets (~1 universal, ~M local, ~sqrt(N) global, ~log N
    super).

All recording is host-side between compiled calls: re-run with
``REPRO_OBS=0`` and the gateway compiles byte-identical programs, the
trace comes out empty, and the run costs one env lookup per span site.

    PYTHONPATH=src python examples/serve_observe.py
"""

import json
import os
import sys

import jax

from repro import obs
from repro.configs import all_configs
from repro.cpm import cpm_array, record
from repro.models import lm
from repro.serve import Engine, Gateway
from repro.serve.gateway import PreemptConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "benchmarks"))
import traffic  # noqa: E402


def main():
    cfg = all_configs()["granite-8b"].smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params, max_len=64)

    trace = traffic.bursty_trace(incumbents=4, long_budget=24, n_bursts=2,
                                 burst=6, gap=10, start=3, seed=0)
    gw = Gateway(engine, slots=4, n_banks=2, chunk=1,
                 preempt=PreemptConfig(min_resident=2, min_remaining=2))
    obs.TRACER.clear()                  # scope the trace to this replay

    print(f"replaying {trace.name}: {len(trace)} requests over "
          f"{gw.pool.slots} slots (telemetry "
          f"{'on' if obs.enabled() else 'OFF — set REPRO_OBS=1'})\n")
    i = 0
    while i < len(trace) or gw.loop.pending():
        while i < len(trace) and (trace.arrivals[i] <= gw.now
                                  or not gw.loop.pending()):
            p = jax.random.randint(jax.random.PRNGKey(100 + i),
                                   (int(trace.lens[i]),), 0, cfg.vocab_size)
            gw.submit(p, int(trace.budgets[i]))
            i += 1
        rep = gw.tick()                 # structured TickReport
        if rep.admitted or rep.restored or rep.preempted or rep.finished:
            print(f"tick {rep.tick:3d} @step {rep.step:3d}: "
                  f"admitted={rep.admitted} restored={rep.restored} "
                  f"preempted={rep.preempted} finished={rep.finished} "
                  f"tick={rep.wall_s * 1e3:.1f}ms")

    # -- export 1: the Chrome/Perfetto trace --------------------------------
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts")
    os.makedirs(here, exist_ok=True)
    trace_path = os.path.join(here, "trace.json")
    counts = obs.validate_chrome_trace(obs.write_trace(trace_path))
    print(f"\nwrote {trace_path} — open at https://ui.perfetto.dev")
    for name in sorted(counts):
        print(f"  {name:<22} x{counts[name]}")

    # -- export 2: the metrics snapshot -------------------------------------
    prom_path = os.path.join(here, "metrics.prom")
    obs.write_metrics(prom_path)
    picks = ("repro_pool_prefill_launches_total",
             "repro_pool_preemptions_total", "repro_pool_restores_total",
             "repro_gateway_requests_total")
    print(f"\nwrote {prom_path}; highlights:")
    for line in open(prom_path):
        if line.startswith(picks):
            print(f"  {line.rstrip()}")

    # -- export 3: the cycle-drift table ------------------------------------
    dev = cpm_array(jax.numpy.arange(64), 48, backend="reference")
    with record() as prog:
        d2 = dev.insert(3, jax.numpy.array([7, 8]))
        d2 = d2.truncate(48)
        d2.compare(9, "lt")
        d2.substring_match(jax.numpy.array([7, 8]))
        d2.super_sum()
    obs.audit(prog, dev)
    print("\npredicted vs measured cycles per op family "
          "(drift 0 = lowerings match the paper's budgets):")
    print(obs.LEDGER.format_drift_table())

    snap = obs.snapshot()
    json_path = os.path.join(here, "metrics.json")
    with open(json_path, "w") as fh:
        json.dump(snap, fh, indent=1, sort_keys=True)
    print(f"\n{len(snap)} metric families snapshotted to {json_path}")


if __name__ == "__main__":
    main()

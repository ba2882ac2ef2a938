"""repro.obs: metrics registry, span tracing, exports, cycle accounting —
and the PR-9 overhead invariants.

The invariants are the contract that makes telemetry safe to leave on:

  * all recording is host-side between compiled calls, so the serving
    stack compiles **byte-identically** with telemetry on or off — same
    compiled-program cache keys, same pallas launch counts (jaxpr-walked
    here, not assumed);
  * span recording never forces a device sync (``block_until_ready`` is
    counted during a decode chunk and must stay at zero);
  * ``REPRO_OBS=0`` nulls spans and ledger records but the metric
    *instruments* keep functioning — they ARE the accounting behind
    ``SessionPool.stats()`` / ``Gateway.stats()``, which old tests read
    unchanged.
"""

import asyncio
import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import all_configs
from repro.cpm import cpm_array, record
from repro.models import lm
from repro.obs import cycles, export, metrics, tracing
from repro.serve import Engine, Gateway
from repro.serve.gateway.loop import TickReport

jax.config.update("jax_platform_name", "cpu")

CFG = all_configs()["granite-8b"].smoke()


@pytest.fixture(scope="module")
def granite():
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    return Engine(CFG, params, max_len=64)


def _prompt(seed, s):
    return jax.random.randint(jax.random.PRNGKey(seed), (s,), 0,
                              CFG.vocab_size)


def _serve_streamed(gw, prompts, budget):
    """Stream every prompt through ``gw.serve`` (streams attached before
    the loop starts); returns ``{rid: client-side time of the first
    token}``."""
    import time

    async def consume(rid):
        first = math.nan
        async for _ in gw.stream(rid):
            if math.isnan(first):
                first = time.perf_counter()
        return rid, first

    async def scenario():
        rids = [gw.submit(p, budget) for p in prompts]
        tasks = [asyncio.ensure_future(consume(r)) for r in rids]
        await asyncio.sleep(0)             # every stream attaches
        await gw.start()
        got = await asyncio.gather(*tasks)
        await gw.stop()
        return dict(got)

    return asyncio.run(scenario())


#: the program's own span and instant names
PROGRAM_SPANS = ("gateway.tick", "gateway.publish", "gateway.request",
                 "pool.admission", "pool.admit_bucket", "pool.prefill",
                 "pool.ensure_pages", "pool.decode_chunk", "pool.retire",
                 "pool.read_row", "alloc.grant")


def _host_events(trace_dir):
    """``(name, stats)`` of every event on the profiler trace's host
    plane in time order (each thread is a line of its own), read back
    with ``jax.profiler.ProfileData``."""
    import warnings
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    with warnings.catch_warnings():        # jaxlib's stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        events = sorted(
            [(e.start_ns, e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events],
            key=lambda ev: ev[0])
    return [(name, stats) for _, name, stats in events]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_series_and_snapshot(self):
        reg = metrics.Registry()
        c = reg.register(metrics.Counter("t_reqs", "requests", ("pool",)))
        g = reg.register(metrics.Gauge("t_occ", "occupancy"))
        c.inc(pool="0")
        c.inc(2, pool="0")
        c.inc(pool="1")
        g.default.set(0.5)
        snap = reg.snapshot()
        assert snap["t_reqs"]["kind"] == "counter"
        assert snap["t_reqs"]["series"] == {'{pool="0"}': 3,
                                            '{pool="1"}': 1}
        assert snap["t_occ"]["series"] == {"": 0.5}
        json.dumps(snap)                       # snapshot is JSON-able

    def test_label_mismatch_raises(self):
        c = metrics.Counter("t_c", "", ("bank",))
        with pytest.raises(ValueError, match="labels"):
            c.labels(pool="0")
        with pytest.raises(ValueError, match="labels"):
            c.labels()

    def test_reregister_idempotent_but_type_change_raises(self):
        reg = metrics.Registry()
        a = reg.register(metrics.Counter("t_x", "", ()))
        assert reg.register(metrics.Counter("t_x", "", ())) is a
        with pytest.raises(ValueError, match="re-registered"):
            reg.register(metrics.Gauge("t_x", "", ()))
        with pytest.raises(ValueError, match="re-registered"):
            reg.register(metrics.Counter("t_x", "", ("pool",)))

    def test_histogram_buckets_cumulative(self):
        h = metrics.Histogram("t_h", "", (), buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        s = h.series()[""]
        assert s["count"] == 4 and s["sum"] == pytest.approx(6.05)
        assert s["buckets"] == {"0.1": 1, "1.0": 3, "+Inf": 4}

    def test_prometheus_text_format(self):
        reg = metrics.Registry()
        c = reg.register(metrics.Counter("t_reqs", "total requests",
                                         ("pool",)))
        c.inc(7, pool="0")
        h = reg.register(metrics.Histogram("t_lat", "latency", (),
                                           buckets=(0.5,)))
        h.observe(0.2)
        text = reg.prometheus_text()
        assert "# HELP t_reqs total requests" in text
        assert "# TYPE t_reqs counter" in text
        assert 't_reqs{pool="0"} 7' in text
        assert 't_lat_bucket{le="0.5"} 1' in text
        assert 't_lat_bucket{le="+Inf"} 1' in text
        assert "t_lat_count 1" in text

    def test_prometheus_escaping_roundtrips_parser(self):
        """Label values with backslashes, quotes and newlines must
        survive exposition — validated by parsing the rendered text back
        with the strict mini-parser, not by substring grep."""
        from repro.obs import promparse
        reg = metrics.Registry()
        c = reg.register(metrics.Counter("t_esc", 'help with "quotes"\n',
                                         ("path",)))
        hostile = 'a\\b"c\nd'
        c.inc(3, path=hostile)
        text = reg.prometheus_text()
        fams = promparse.parse(text)
        assert fams["t_esc"].series() == {(("path", hostile),): 3.0}
        assert fams["t_esc"].help.startswith("help with")

    def test_prometheus_exposition_passes_strict_parser(self, granite):
        """The whole live registry — after real serving traffic, with
        histograms and derived summary families — must satisfy the
        mini-parser's HELP/TYPE-ordering and histogram-consistency
        checks (the same gate CI runs on a /metrics scrape)."""
        from repro.obs import promparse
        lat = metrics.histogram("t_exposition_seconds", "a histogram "
                                "family beside the serving series", ())
        for v in (0.002, 0.03, 0.7):
            lat.observe(v)
        gw = Gateway(granite, slots=2, chunk=2)
        gw.result(gw.submit(_prompt(60, 8), 4, deadline_steps=100))
        fams = promparse.parse(metrics.REGISTRY.prometheus_text())
        assert "repro_gateway_requests_total" in fams
        hists = [f for f in fams.values() if f.type == "histogram"]
        assert hists                         # consistency checks all ran
        for f in hists:
            assert f.series("_count")        # _sum/_count present

    def test_series_property_shim(self):
        fam = metrics.Counter("t_shim", "", ("pool",))

        class Layer:
            hits = metrics.series_property("hits")

            def __init__(self):
                self._obs_series = {"hits": fam.labels(pool="p")}

        layer = Layer()
        layer.hits += 3
        assert layer.hits == 3
        assert fam.labels(pool="p").value == 3

    def test_disabled_instruments_still_function(self, monkeypatch):
        """REPRO_OBS=0 skips registration only — the instrument still
        counts (it backs the stats() views)."""
        monkeypatch.setenv("REPRO_OBS", "0")
        c = metrics.counter("t_disabled_counter", "", ())
        c.inc(5)
        assert c.default.value == 5
        assert metrics.REGISTRY.get("t_disabled_counter") is None


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def test_span_nesting_wall_and_virtual_clocks(self):
        tr = tracing.Tracer()
        clock = {"v": 10}
        with tr.span("outer", vclock=lambda: clock["v"]) as sp:
            sp.args["note"] = "x"
            with tr.span("inner"):
                pass
            clock["v"] += 4
        inner, outer = tr.spans("inner")[0], tr.spans("outer")[0]
        assert inner.depth == 1 and outer.depth == 0
        assert outer.dur >= inner.dur >= 0
        assert outer.vstep == 10 and outer.vdur == 4
        assert inner.vstep is None
        assert outer.args == {"note": "x"}

    def test_instants_and_counters(self):
        tr = tracing.Tracer()
        tr.instant("grant", vstep=3, args={"pages": 2})
        tr.counter("queue_depth", 7)
        ev = tr.spans("grant")[0]
        assert ev.dur is None and ev.vstep == 3
        assert tr.spans("queue_depth")[0].cat.startswith("__counter__.")

    def test_disabled_records_nothing(self, monkeypatch):
        monkeypatch.setenv("REPRO_OBS", "0")
        tr = tracing.Tracer()
        with tr.span("s") as sp:
            sp.args["ignored"] = 1         # null handle absorbs writes
        tr.instant("i")
        tr.counter("c", 1)
        assert tr.spans() == []

    def test_annotation_arguments_are_scalars(self):
        """The profiler's copy of a span's arguments: numbers stay
        numbers, lists and strings become short strings free of the
        characters that delimit the profiler's encoding."""
        got = tracing._scalars({"sids": [3, 4], "site": "a,b#c=d",
                                "n": 5, "f": 1.5, "gone": None,
                                "long": "x" * 500})
        assert got == {"sids": "3 4", "site": "a b c d", "n": 5, "f": 1.5,
                       "long": "x" * 64}

    def test_compile_event_names_open_span(self):
        fam = metrics.REGISTRY.get("repro_runtime_compiles_total")
        before = fam.default.value
        tracing.TRACER.clear()
        x = np.arange(7.0)
        with tracing.span("outer.work"):
            jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
        evs = tracing.TRACER.spans("runtime.compile")
        assert [e.args["span"] for e in evs] == ["outer.work"]
        assert evs[0].args["seconds"] >= 0 and evs[0].cat == "runtime"
        assert fam.default.value == before + 1

    def test_thread_isolation(self):
        import threading
        tr = tracing.Tracer()
        done = threading.Event()

        def worker():
            with tr.span("w"):
                pass
            done.set()

        with tr.span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert done.is_set()
        w, m = tr.spans("w")[0], tr.spans("main")[0]
        assert w.tid != m.tid
        assert w.depth == 0                # sibling stacks, not nested


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

class TestExport:
    def test_chrome_trace_structure_and_validation(self):
        tr = tracing.Tracer()
        with tr.span("tick", cat="gateway", vclock=lambda: 5):
            tr.instant("grant")
        tr.counter("depth", 3)
        obj = export.chrome_trace(tr)
        counts = export.validate_chrome_trace(obj)
        assert counts == {"tick": 1, "grant": 1, "depth": 1}
        evs = {e["name"]: e for e in obj["traceEvents"] if e["ph"] != "M"}
        assert evs["tick"]["ph"] == "X" and evs["tick"]["dur"] >= 0
        assert evs["tick"]["args"]["vstep"] == 5
        assert evs["grant"]["ph"] == "i"
        assert evs["depth"]["ph"] == "C"
        assert any(e["ph"] == "M" for e in obj["traceEvents"])
        json.dumps(obj)                    # serializable as-is

    def test_validation_rejects_malformed(self):
        with pytest.raises(ValueError, match="traceEvents"):
            export.validate_chrome_trace({"events": []})
        bad = {"traceEvents": [{"ph": "X", "name": "a", "pid": 1,
                                "ts": 0.0, "dur": -1.0}]}
        with pytest.raises(ValueError, match="negative"):
            export.validate_chrome_trace(bad)
        with pytest.raises(ValueError, match="phase"):
            export.validate_chrome_trace(
                {"traceEvents": [{"ph": "?", "name": "a", "pid": 1}]})

    def test_write_trace_roundtrip(self, tmp_path):
        tr = tracing.Tracer()
        with tr.span("s"):
            pass
        path = tmp_path / "trace.json"
        export.write_trace(str(path), tr)
        assert export.validate_chrome_trace(
            json.loads(path.read_text())) == {"s": 1}

    def test_write_metrics_formats(self, tmp_path):
        metrics.counter("t_wm", "help text", ()).inc(2)
        prom = tmp_path / "m.prom"
        export.write_metrics(str(prom))
        assert "t_wm 2" in prom.read_text()
        j = tmp_path / "m.json"
        export.write_metrics(str(j), fmt="json")
        assert json.loads(j.read_text())["t_wm"]["series"][""] == 2


# ---------------------------------------------------------------------------
# cycle accounting
# ---------------------------------------------------------------------------

class TestCycles:
    def test_audit_zero_drift_across_families(self):
        """The op-table budgets equal the jaxpr-measured scan trips for
        every audited family — the live restatement of the PR-3/4
        model-vs-measured equality."""
        dev = cpm_array(jnp.arange(64), 48, backend="reference")
        with record() as prog:
            d2 = dev.insert(3, jnp.array([7, 8]))
            d2 = d2.truncate(48)
            d2.compare(9, "lt")
            d2.substring_match(jnp.array([7, 8]))
            d2.count(9, "lt")          # derived: +1 drain, not a scan trip
            d2.super_sum()
        led = cycles.CycleLedger()
        rows = cycles.audit(prog, dev, ledger=led)
        assert [r["drift"] for r in rows] == [0] * len(rows)
        sub = next(r for r in rows if r["op"] == "substring_match")
        assert sub["measured_trips"] == sub["predicted_scan"] == 2
        sup = next(r for r in rows if r["op"] == "super_sum")
        assert sup["measured_trips"] == sup["predicted_scan"] > 0
        table = led.drift_table()
        assert all(r["drift"] == 0 for r in table)
        assert {r["family"] for r in table} >= {"move", "compare",
                                                "search", "compute"}
        led.format_drift_table()           # renders without error

    def test_steps_report_feeds_ledger(self):
        ledger_before = {r["family"]: r["predicted"]
                         for r in cycles.LEDGER.drift_table()}
        dev = cpm_array(jnp.arange(32), 24, backend="reference")
        with record() as prog:
            dev.substring_match(jnp.array([1, 2, 3]))
        rep = prog.steps_report(32)
        assert rep["total"] == 3
        after = {r["family"]: r["predicted"]
                 for r in cycles.LEDGER.drift_table()}
        assert after["search"] == ledger_before.get("search", 0) + 3

    def test_steps_report_disabled_skips_ledger(self, monkeypatch):
        monkeypatch.setenv("REPRO_OBS", "0")
        before = {r["family"]: r["predicted"]
                  for r in cycles.LEDGER.drift_table()}
        dev = cpm_array(jnp.arange(32), 24, backend="reference")
        with record() as prog:
            dev.substring_match(jnp.array([1, 2]))
        prog.steps_report(32)
        after = {r["family"]: r["predicted"]
                 for r in cycles.LEDGER.drift_table()}
        assert after == before

    def test_audit_refuses_inside_trace(self):
        dev = cpm_array(jnp.arange(16), 16, backend="reference")
        with record() as prog:
            dev.compare(3, "lt")

        def traced(x):
            cycles.audit(prog, dev)
            return x

        with pytest.raises(RuntimeError, match="active jax trace"):
            jax.make_jaxpr(traced)(jnp.zeros(()))


# ---------------------------------------------------------------------------
# overhead invariants over the serving stack
# ---------------------------------------------------------------------------

def _chunk_launches(pool):
    """Pallas launch count of a freshly built decode chunk (bypasses the
    compiled-program cache so each call re-lowers under the current
    REPRO_OBS)."""
    from repro.cpm.program import count_pallas_calls
    run = pool._build_chunk(pool.slots, pool.chunk, pool.n_banks,
                            "pallas", True, pool.page_size,
                            pool.pages_per_bank)
    pt = np.full((pool.slots, pool.C), pool.total_pages, np.int32)
    return count_pallas_calls(
        run, pool.engine.params, pool.cur, pool.caches, pool.pos,
        jnp.asarray(pool.live), jnp.zeros((pool.slots,), jnp.int32),
        jnp.asarray(pool._temp), jnp.asarray(pool._topk),
        jnp.asarray(pool._topp), [b.data for b in pool.banks],
        [b.lens for b in pool.banks], jnp.asarray(pt), pool.tok_lens,
        jax.random.PRNGKey(7))


class TestOverheadInvariants:
    def test_chunk_launch_count_identical_obs_on_off(self, granite,
                                                     monkeypatch):
        """Telemetry can never change what compiles: the decode chunk
        lowers to the same pallas launch count with REPRO_OBS on or off
        (jaxpr-walked, the PR-6 trace-safety rule made enforceable)."""
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2,
                                    page_size=8, pages_per_bank=8,
                                    bank_backend="pallas",
                                    bank_interpret=True)
        monkeypatch.setenv("REPRO_OBS", "1")
        n_on = _chunk_launches(pool)
        monkeypatch.setenv("REPRO_OBS", "0")
        n_off = _chunk_launches(pool)
        assert n_on == n_off == 3 * pool.n_banks

    def test_program_cache_keys_identical_obs_on_off(self, granite,
                                                     monkeypatch):
        """The compiled-program cache is keyed identically with telemetry
        on or off — REPRO_OBS is not (and must never become) a compile
        discriminator."""
        def run_workload():
            tracing.TRACER.clear()
            gw = Gateway(granite, slots=2, chunk=2)
            _serve_streamed(gw, [_prompt(500 + i, 8) for i in range(3)], 4)
            names = {e.name for e in tracing.TRACER.spans()}
            return ({k for k in granite._programs if k[0].startswith("pool")},
                    names)

        monkeypatch.setenv("REPRO_OBS", "1")
        for k in list(granite._programs):
            if k[0].startswith("pool"):
                del granite._programs[k]
        keys_on, names_on = run_workload()
        monkeypatch.setenv("REPRO_OBS", "0")
        for k in list(granite._programs):
            if k[0].startswith("pool"):
                del granite._programs[k]
        keys_off, names_off = run_workload()
        assert keys_on == keys_off and keys_on
        assert set(PROGRAM_SPANS) <= names_on and not names_off

    def test_no_device_sync_inside_chunk(self, granite, monkeypatch):
        """Span recording must not force a device sync: zero
        block_until_ready calls during the traced decode chunk."""
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2)
        pool.submit(_prompt(600, 8), 6)
        pool.step()                        # admission + first chunk, warm
        syncs = {"n": 0}
        real = jax.block_until_ready

        def counting(x):
            syncs["n"] += 1
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        pool._decode_chunk()
        assert syncs["n"] == 0
        assert tracing.TRACER.spans("pool.decode_chunk")

        # nor do the spans between chunks: page top-up and its grant,
        # retirement, the row reads and the gateway's publish (pages of 4
        # tokens, so the session outgrows its admission grant)
        monkeypatch.setattr(jax, "block_until_ready", real)
        gw = Gateway(granite, slots=2, chunk=2, page_size=4)
        gw.submit(_prompt(601, 8), 5)
        gw.tick()
        tracing.TRACER.clear()
        monkeypatch.setattr(jax, "block_until_ready", counting)
        while gw.loop.pending():
            gw.tick()
        assert syncs["n"] == 0
        names = {e.name for e in tracing.TRACER.spans()}
        assert {"pool.ensure_pages", "pool.retire", "pool.read_row",
                "gateway.publish", "gateway.request", "alloc.grant"} <= names

    def test_disabled_pool_keeps_stats_but_records_no_spans(
            self, granite, monkeypatch):
        monkeypatch.setenv("REPRO_OBS", "0")
        tracing.TRACER.clear()
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2)
        pool.submit(_prompt(610, 8), 4)
        pool.drain()
        st = pool.stats()                  # thin views keep working
        assert st["prefill_launches"] == 1 and st["emitted"] == 4
        assert tracing.TRACER.spans() == []


# ---------------------------------------------------------------------------
# serving-layer integration
# ---------------------------------------------------------------------------

class TestServingIntegration:
    def test_tick_report_schema_and_dict_fallback(self, granite):
        tracing.TRACER.clear()
        gw = Gateway(granite, slots=2, chunk=2)
        gw.submit(_prompt(700, 8), 4)
        rep = gw.tick()
        assert isinstance(rep, TickReport)
        assert rep.tick == 0 and rep.step == gw.pool.decode_steps
        assert rep.admitted == 1 and rep.restored == 0
        assert rep.emitted >= 1
        # the chunk's dispatch is a span nested in the tick's span
        (tick,) = tracing.TRACER.spans("gateway.tick")
        (chunk,) = tracing.TRACER.spans("pool.decode_chunk")
        assert tick.args["tick"] == rep.tick and chunk.depth > tick.depth
        assert tick.ts <= chunk.ts
        assert chunk.ts + chunk.dur <= tick.ts + tick.dur
        assert rep.wall_s >= tick.dur >= chunk.dur >= 0.0
        assert chunk.vstep + chunk.vdur == rep.step
        assert rep["waiting"] == rep.waiting          # field access
        assert rep["preemptions"] == 0                # stats fallback
        assert rep.get("no_such_key", 42) == 42
        assert rep.asdict()["stats"]["prefill_launches"] == 1
        total_emitted = rep.emitted
        while gw.loop.pending():
            total_emitted += gw.tick().emitted
        assert total_emitted == gw.pool.total_emitted

    def test_pool_stats_equal_registry_series(self, granite):
        """stats() is a thin view: the registry series for this pool's
        label hold the very same numbers."""
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2)
        for i in range(3):
            pool.submit(_prompt(710 + i, 8), 4)
        pool.drain()
        st = pool.stats()
        for stat_key, metric_name in [
                ("prefill_launches", "repro_pool_prefill_launches_total"),
                ("admit_batches", "repro_pool_admit_batches_total"),
                ("decode_steps", "repro_pool_decode_steps_total"),
                ("emitted", "repro_pool_emitted_total"),
                ("pages_free", "repro_pool_pages_free")]:
            fam = metrics.REGISTRY.get(metric_name)
            assert fam is not None, metric_name
            assert fam.labels(pool=pool._pool_label).value == st[stat_key]

    def test_gateway_spans_cover_every_layer(self, granite):
        tracing.TRACER.clear()
        gw = Gateway(granite, slots=2, chunk=2)
        for i in range(3):                 # oversubscribe: forces parking
            gw.submit(_prompt(720 + i, 8), 6)
        gw.tick()                          # admit the first window
        gw.pool.park(gw.request(0).sid)    # exercise park/restore spans
        while gw.loop.pending():
            gw.tick()
        counts = export.validate_chrome_trace(export.chrome_trace())
        for name in ("gateway.tick", "pool.admission", "pool.prefill",
                     "pool.decode_chunk", "pool.park", "pool.restore"):
            assert counts.get(name, 0) >= 1, (name, sorted(counts))

    def test_request_wall_stamps_in_order(self, granite):
        """Every request's wall stamps run submit <= seat <= first stream
        put <= finish, the client sees its first token after the put, and
        the ``gateway.request`` event carries the same stamps."""
        tracing.TRACER.clear()
        gw = Gateway(granite, slots=2, chunk=2)
        first_seen = _serve_streamed(
            gw, [_prompt(900 + i, 8) for i in range(5)], 5)
        events = {e.args["rid"]: e.args
                  for e in tracing.TRACER.spans("gateway.request")}
        assert sorted(events) == sorted(first_seen)
        for rid, seen in first_seen.items():
            req = gw.request(rid)
            stamps = [req.submitted_s, req.seated_s, req.first_stream_s,
                      req.finished_s]
            assert all(math.isfinite(t) for t in stamps), stamps
            assert stamps == sorted(stamps)
            assert req.first_stream_s <= seen
            assert events[rid]["sid"] == req.sid
            assert [events[rid][k] for k in (
                "submitted_s", "seated_s", "first_stream_s",
                "finished_s")] == stamps

    def test_obs_package_exports(self):
        assert obs.enabled() in (True, False)
        assert callable(obs.span) and callable(obs.audit)
        assert obs.REGISTRY is metrics.REGISTRY
        assert obs.TRACER is tracing.TRACER


# ---------------------------------------------------------------------------
# the profiler sink
# ---------------------------------------------------------------------------

class TestProfilerSink:
    def test_spans_land_on_profiler_host_plane(self, granite, tmp_path):
        """Under ``jax.profiler.trace`` a served run's spans are on the
        trace's host plane, with their arguments, those set inside the
        span included."""
        gw = Gateway(granite, slots=2, chunk=2)
        with jax.profiler.trace(str(tmp_path)):
            _serve_streamed(gw, [_prompt(950 + i, 8) for i in range(3)], 5)
        events = _host_events(str(tmp_path))
        names = {n for n, _ in events}
        for name in ("gateway.tick", "gateway.publish", "pool.decode_chunk",
                     "pool.read_row", "pool.admit_bucket", "gateway.request"):
            assert name in names, (name, sorted(names)[:40])
        reads = [st for n, st in events if n == "pool.read_row"]
        assert {"stream", "retire"} <= {st["site"] for st in reads}
        assert all({"sid", "pages"} <= set(st) for st in reads)
        publish = [st for n, st in events if n == "gateway.publish"]
        assert all({"streams", "finished"} <= set(st) for st in publish)
        assert sum(st["finished"] for st in publish) == 3
        ticks = [st["tick"] for n, st in events if n == "gateway.tick"]
        assert ticks == sorted(ticks) and ticks[0] == 0

    def test_disabled_records_into_neither_sink(self, granite, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("REPRO_OBS", "0")
        tracing.TRACER.clear()
        gw = Gateway(granite, slots=2, chunk=2)
        with jax.profiler.trace(str(tmp_path)):
            _serve_streamed(gw, [_prompt(970 + i, 8) for i in range(2)], 4)
            tracing.TRACER.instant("an.instant")
        names = {n for n, _ in _host_events(str(tmp_path))}
        assert not names & (set(PROGRAM_SPANS) | {"an.instant"})
        assert tracing.TRACER.spans() == []
        req = gw.request(0)                # the request's own stamps stay
        assert req.submitted_s <= req.seated_s <= req.finished_s

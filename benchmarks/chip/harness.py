"""The served path of one cell, driven from the client's side.

Shared by ``run.py`` (one measured run), ``sweep.py`` (the knee) and
``control.py`` (the readings that set the correctness limit).  The
program under test is ``repro``: its ``Engine`` and ``Gateway``, driven
through the asyncio face that ``serve/http.py`` mounts (``start``,
``asubmit``, ``stream``).  Everything else here is the benchmark's own.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

import numpy as np

import families

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
DRAIN_S = 60.0          # a window's requests may finish this long after it
TRACE_AT = 0.25         # the trace starts this share into the window ...
TRACE_S = 3.0           # ... and lasts this long (or half the window)
REF_MIN_TOKENS = 800    # served tokens the reference compares ...
REF_MAX_REQUESTS = 8    # ... from at most this many requests


class NoChip(SystemExit):
    pass


# -- the cell and its files ---------------------------------------------------
def load_cell(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    from traffic import load
    return bench, cell, cfg, load(cell["traffic"])


def rehearsal_sizes(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """A tiny configuration and traffic of the same shape, for the CPU:
    the model at its family's ``tiny`` size, a small pool, short prompts
    and answers."""
    cfg = dict(families.of(cfg).tiny(cfg),
               pool=dict(cfg["pool"], slots=4, max_len=64, page_size=8,
                         chunk=4))
    classes = [8, 16, 24, 32][:len(traffic["prompt"]["classes"])]
    traffic = dict(traffic,
                   arrival=dict(traffic["arrival"], rate_rps=4.0),
                   prompt=dict(traffic["prompt"], classes=classes),
                   output=dict(traffic["output"], median=8, min=2, max=24),
                   warmup=dict(traffic["warmup"], prompt_lens=classes))
    return cfg, traffic


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- JAX and the device ---------------------------------------------------------
def start_jax(rehearse: bool, chips: int):
    """Import JAX with the compile cache in the checkout, and return the
    device record.  Off a TPU (or with fewer chips than asked) this raises
    ``NoChip`` unless ``rehearse``."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoChip(f"no program under test: {src}/repro is missing")
    sys.path.insert(0, str(src))
    if rehearse:                 # nothing of a CPU run is worth keeping
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        import jax
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # every program of the cell stays: an evicting size limit set in
        # the environment would make later runs compile again
        jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips if not rehearse else 1}


class CompileClock:
    """Programs JAX lowered (compiled or loaded from the cache), counted by
    a ``jax.monitoring`` listener, and the seconds of backend compiles."""

    def __init__(self):
        import jax
        self.programs = 0
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event.endswith("jaxpr_to_mlir_module_duration"):
                self.programs += 1
            elif event.endswith("backend_compile_duration"):
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


# -- the system under test ------------------------------------------------------
def model_config(cfg: dict):
    """The program's ``ModelConfig``, from the configuration's family."""
    return families.of(cfg).model_config(cfg)


def vocab(cfg: dict) -> int:
    """Token ids run from 0 to this: the traffic draws its prompts and the
    checks bound the served ids by it."""
    return model_config(cfg).vocab_size


def make_params(cfg: dict, seed: int):
    """The program's weights, drawn on the device in one jitted call."""
    import jax
    from repro.models import lm
    import weights
    vp = lm.padded_vocab(model_config(cfg))
    make = jax.jit(lambda k: families.of(cfg).program_params(
        cfg, weights.canonical(cfg, k), vp))
    return jax.block_until_ready(make(weights.key(seed)))


def build_gateway(cfg: dict, params):
    from repro.serve import Engine, GenConfig
    from repro.serve.gateway import Gateway
    pool = cfg["pool"]
    engine = Engine(model_config(cfg), params, max_len=pool["max_len"])
    return Gateway(engine, slots=pool["slots"], chunk=pool["chunk"],
                   page_size=pool["page_size"], preempt=pool["preempt"],
                   gen=GenConfig(max_new_tokens=1, temperature=0.0))


def warm_up(gw, cfg: dict, traffic: dict, seed: int) -> None:
    """Run every admission shape the window can meet once: each (prompt
    length, bucket size) the traffic file lists, and the token-bank
    gathers and the allocator's releases of every page count a session
    of this traffic can hold.  :func:`warm_stream` does the rest."""
    import jax
    from traffic import warm_shapes
    pool, rng = gw.pool, np.random.default_rng(seed ^ 0x5EED)
    ids = vocab(cfg)
    for s, k in warm_shapes(traffic):
        rids = [gw.submit(rng.integers(0, ids, s, dtype=np.int32), 1)
                for _ in range(k)]
        while not all(gw.request(r).done for r in rids):
            gw.tick()
    # a session holds, reads and frees any page count from its admission
    # grant up to its longest prompt + answer (+ one chunk of slack)
    lens = traffic["warmup"]["prompt_lens"]
    lo = pool.pages_for(min(lens) + 1)
    hi = min(pool.C, pool.pages_for(max(lens) + traffic["output"]["max"]
                                    + pool.chunk))
    for bank in pool.banks:                # _read_row: one gather per count
        for n in range(lo, hi + 1):
            jax.block_until_ready(bank.gather(np.arange(n, dtype=np.int32)))
    grants = sorted({1} | {pool.pages_for(s + 1) for s in lens}, reverse=True)
    for n in range(lo, hi + 1):            # releases of every page count,
        slot, left = pool.alloc.alloc(), n  # built from the grant sizes
        for g in grants:
            while left >= g:
                pool.alloc.alloc_pages(slot, g, 0, pool.pages_per_bank)
                left -= g
        pool.alloc.free(slot)

async def warm_stream(gw, cfg: dict, traffic: dict, seed: int) -> None:
    """The decode chunk, page top-ups, streaming peeks and retirement,
    through the asyncio face the window drives (same event loop)."""
    import jax
    pool, rng = gw.pool, np.random.default_rng(seed ^ 0xD0DE)
    lens = traffic["warmup"]["prompt_lens"]
    await gw.start()
    rids = [await gw.asubmit(
        rng.integers(0, vocab(cfg), lens[i % len(lens)],
                     dtype=np.int32), 2 * pool.chunk + 1)
        for i in range(pool.slots)]
    for r in rids:
        async for _ in gw.stream(r):
            pass
    await gw.stop()
    gw.collect_delivered()
    jax.block_until_ready(pool.caches)


# -- the open-loop window -------------------------------------------------------
@dataclasses.dataclass
class Rec:
    """One request as the client saw it (host ``perf_counter`` seconds)."""
    due: float
    prompt: np.ndarray
    budget: int
    in_window: bool
    submitted: float = math.nan
    admitted: float = math.nan      # end of the tick that seated it
    admit_tick: int = -1            # that tick's index (bench.tick's "tick")
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    final: np.ndarray | None = None
    error: str = ""
    sess: object = None
    rid: int = -1

    @property
    def done(self) -> bool:
        return self.final is not None


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    recs: list
    pool_start: dict
    pool_end: dict
    programs: int                   # programs lowered inside the window
    lateness: list                  # submit - due, seconds, per request
    trace_span: tuple = ()          # host (start, end) of the trace

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    @property
    def measured(self) -> list:
        return [r for r in self.recs if r.in_window]


def _pool_counters(pool) -> dict:
    return {"decode_steps": pool.decode_steps,
            "decode_emitted": pool._decode_emitted, "slots": pool.slots}


async def serve_window(gw, reqs, seconds: float, clock: CompileClock,
                 trace_dir: str | None = None) -> Window:
    """Send ``reqs`` open-loop, each at its due time, through the Gateway's
    asyncio face; record submit, admission and token arrival times.  Sends
    go on past the window until its requests have finished (or
    ``DRAIN_S`` has passed), then the gateway stops."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    pool = gw.pool
    recs = [Rec(r.due, r.prompt, r.budget, r.in_window) for r in reqs]
    watch: dict[int, Rec] = {}

    tick, count = gw.loop.tick, [0]

    def traced_tick():
        i = count[0]
        count[0] += 1
        with annotate("bench.tick", tick=i):
            rep = tick()
        now = time.perf_counter()
        for key in list(watch):
            rec = watch[key]
            if rec.sess.first_admit_step >= 0:
                rec.admitted, rec.admit_tick = now, i
                watch.pop(key, None)
        return rep

    gw.loop.tick = traced_tick
    out = {}

    async def client(rec: Rec):
        rec.submitted = time.perf_counter()
        try:
            with annotate("bench.submit"):
                rid = rec.rid = await gw.asubmit(rec.prompt, rec.budget)
                rec.sess = gw.pool.table.get(gw.request(rid).sid)
                watch[id(rec)] = rec
            async for chunk in gw.stream(rid):
                now = time.perf_counter()
                with annotate("bench.deliver"):
                    rec.times.extend([now] * len(chunk))
                    rec.tokens.extend(np.asarray(chunk).tolist())
            rec.final = np.asarray(gw.request(rid).tokens)
        except Exception as e:                 # counted as failed
            rec.error = f"{type(e).__name__}: {e}"

    async def tracer(t0):
        span = min(TRACE_S, seconds / 2)
        await asyncio.sleep(max(0.0, t0 + TRACE_AT * seconds
                                - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        a = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        b = time.perf_counter()
        await asyncio.sleep(span)
        c = time.perf_counter()
        await asyncio.to_thread(jax.profiler.stop_trace)
        out["trace_span"] = ((a + b) / 2, c)

    async def sender(t0, tasks, window_tasks, stop):
        for rec in recs:
            wait = t0 + rec.due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            if stop.is_set():
                return
            task = asyncio.ensure_future(client(rec))
            tasks.append(task)
            if rec.in_window:
                window_tasks.append(task)

    async def main():
        await gw.start()
        t0 = time.perf_counter()
        out["t0"] = t0
        out["pool_start"] = _pool_counters(pool)
        programs0 = clock.programs
        trace_task = (asyncio.ensure_future(tracer(t0))
                      if trace_dir else None)
        tasks, window_tasks, stop = [], [], asyncio.Event()
        send = asyncio.ensure_future(sender(t0, tasks, window_tasks, stop))
        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        out["pool_end"] = _pool_counters(pool)
        out["programs"] = clock.programs - programs0
        while len(window_tasks) < sum(r.in_window for r in recs):
            await asyncio.sleep(0.01)      # the last sends of the window
        left = t0 + seconds + DRAIN_S - time.perf_counter()
        await asyncio.wait(window_tasks, timeout=max(left, 0.0))
        if trace_task is not None:
            await trace_task
        stop.set()
        send.cancel()
        for t in tasks:
            t.cancel()
        await asyncio.gather(send, *tasks, return_exceptions=True)
        await gw.stop()
        for rec in recs:                 # the load sent past the window
            if rec.rid >= 0 and not gw.request(rec.rid).done:
                gw.cancel(rec.rid)
        gw.collect_delivered()

    await main()
    gw.loop.tick = tick
    for rec in recs:
        rec.sess = None
    return Window(t0=out["t0"], seconds=seconds, recs=recs,
                  pool_start=out["pool_start"], pool_end=out["pool_end"],
                  programs=out["programs"],
                  lateness=[r.submitted - (out["t0"] + r.due) for r in recs
                            if r.in_window and not math.isnan(r.submitted)],
                  trace_span=out.get("trace_span", ()))


def free() -> None:
    """Let go of the program's state on the device, once the caller has
    dropped its references to the gateway and the weights."""
    import jax
    gc.collect()
    jax.clear_caches()


def device_peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- correctness -----------------------------------------------------------------
def served_checks(win: Window, vocab: int) -> dict:
    """Exact checks on every request due in the window: it finished, the
    stream delivered exactly the tokens of its final answer, exactly its
    budget of them, all ids in the vocabulary."""
    unfinished = mismatched = 0
    for r in win.measured:
        if not r.done:
            unfinished += 1
            continue
        new = r.final[len(r.prompt):]
        if (len(new) != r.budget or list(new) != r.tokens
                or not np.array_equal(r.final[:len(r.prompt)], r.prompt)
                or new.min() < 0 or new.max() >= vocab):
            mismatched += 1
    return {"unfinished": unfinished, "stream_mismatch": mismatched}


def sample_for_reference(win: Window, seed: int) -> list:
    """Finished requests drawn from the seed, the longest answer first,
    until ``REF_MIN_TOKENS`` served tokens or ``REF_MAX_REQUESTS``
    requests."""
    done = [r for r in win.measured if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: r.budget)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    pick, total = [longest], longest.budget
    for i in order:
        if total >= REF_MIN_TOKENS or len(pick) >= REF_MAX_REQUESTS:
            break
        pick.append(rest[i])
        total += rest[i].budget
    return pick


def logit_gaps(cfg: dict, seed: int, recs: list, fp8_control: bool = False):
    """Per sampled request, the widest gap (in standard deviations of the
    reference's logits at that position) by which a served token lies
    below the float32 reference's best.  With ``fp8_control`` also the
    same gap for the token that the float8 reference puts first."""
    import jax
    import jax.numpy as jnp
    import weights
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    w = jax.jit(lambda k: weights.canonical(cfg, k))(weights.key(seed))
    width = cfg["pool"]["max_len"]

    @functools.partial(jax.jit, static_argnums=4)
    def gaps(w, seq, lo, hi, fp8_first):
        logits = ref.logits(w, cfg, seq)                     # (S, V)
        best = jnp.max(logits, -1)
        sd = jnp.std(logits, -1)
        served = jnp.roll(seq, -1)                           # next token
        pos = jnp.arange(seq.shape[0])
        take = (pos >= lo) & (pos < hi)
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        prog = jnp.max(jnp.where(take, (best - got) / sd, 0.0))
        if not fp8_first:
            return prog, jnp.zeros(())
        lq = ref.logits(w, cfg, seq, fp8=True)
        first = jnp.argmax(lq, -1)
        gq = jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
        return prog, jnp.max(jnp.where(take, (best - gq) / sd, 0.0))

    out = []
    for r in recs:
        seq = np.zeros((width,), np.int32)
        seq[:len(r.final)] = r.final
        # positions plen-1 .. total-2 predict the served tokens
        lo, hi = len(r.prompt) - 1, len(r.final) - 1
        p, c = gaps(w, jnp.asarray(seq), lo, hi, fp8_control)
        out.append((float(p), float(c)))
    del w
    return out

"""Benchmark harness — one function per paper claim/table.

The paper (CS.DC 2006, "Concurrent Processing Memory") makes
instruction-cycle *complexity* claims rather than wall-clock tables:

  T1  universal ops (insert/delete/move/match)      ~1 cycle
  T2  substring search of an M-needle               ~M cycles        (§5)
  T3  field compare + M-bin histogram               ~1 / ~M cycles   (§6)
  T4  global sum / limit, two-phase                 ~sqrt(N) cycles  (§7.4)
  T5  sorting, local exchange + global move         ~sqrt(N) cycles  (§7.7)
  T6  1-D template match                            ~M^2 cycles      (§7.6)
  T7  line detection at radius D                    ~D^2 cycles      (§7.9)
  T8  super-connectivity upgrade                    sqrt(N) -> log N (§8)
      — both as collective schedules (ring vs tree all-reduce) and as the
      CPMArray ``super_sum``/``super_limit`` ops, whose jaxpr-measured
      trip counts the ``cpm_ops`` scenario asserts <= ~2*log2(N)+1.

Each bench validates the claim in the *concurrent-step* currency (derived
column) and reports wall-clock us_per_call of the TPU-adapted JAX lowering.
Step counts come from the op table (``repro.cpm.optable``) — the single
source of truth the `CPMArray` surface registers each op in — and the
``cpm_ops`` scenario cross-checks them against trip counts *measured* from
the lowered jaxprs; ``program_fusion`` does the same for whole recorded
instruction streams (`repro.cpm.program`) and asserts the fused-pipeline
pallas_call-count reduction.  Output: ``name,us_per_call,derived`` CSV.

Usage: ``python benchmarks/run.py [scenario ...] [--json [PATH]]``
(default: all scenarios; bare ``--json`` writes one
``BENCH_<scenario>.json`` per scenario at the repo root).
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cpm import OP_TABLE, cpm_array, op_steps
from repro.cpm.reference import (comparable, computable, movable, pe_array,
                                 searchable)

ROWS = []


def timeit(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))             # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def run_subbench(script: str, prefix: str):
    """Run a bench script in a fresh 8-host-device subprocess (multi-device
    setups need XLA flags set before jax imports) and collect its CSV rows."""
    import os
    import subprocess
    preamble = (
        'import os\n'
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
        'os.environ.setdefault("JAX_PLATFORMS", "cpu")\n')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", preamble + script],
                       capture_output=True, text=True, cwd=root,
                       env=dict(os.environ, PYTHONPATH="src",
                                JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, f"{prefix} subbench failed:\n{r.stderr}"
    for line in r.stdout.strip().splitlines():
        if line.startswith(prefix):
            print(line, flush=True)
            parts = line.split(",")
            ROWS.append((parts[0], float(parts[1]), parts[2]))


# -- T1: universal ops ------------------------------------------------------

def bench_universal_ops():
    for n in (4096, 65536, 1048576):
        x = jnp.arange(n)
        f = jax.jit(lambda x: movable.shift_range(x, n // 4, n // 2, 1))
        row(f"T1_move_range_N{n}", timeit(f, x), "steps=1")
        vals = jnp.array([7, 8])
        g = jax.jit(lambda x: movable.insert(x, n // 4, vals, n - 4))
        row(f"T1_insert_N{n}", timeit(g, x), "steps=2")
        h = jax.jit(lambda x: pe_array.count_matches(comparable.compare(x, n // 2, "lt")))
        row(f"T1_compare_count_N{n}", timeit(h, x), "steps=1")


# -- T2: substring ----------------------------------------------------------

def bench_substring():
    n = 65536
    hay = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, 4)
    for m in (2, 8, 32):
        nee = jax.random.randint(jax.random.PRNGKey(1), (m,), 0, 4)
        f = jax.jit(searchable.substring_match)
        us = timeit(f, hay, nee)
        row(f"T2_substring_M{m}_N{n}", us, f"steps={m}")


# -- T3: histogram ----------------------------------------------------------

def bench_histogram():
    n = 262144
    x = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, 256)
    for m in (8, 64):
        edges = jnp.linspace(0, 256, m + 1).astype(jnp.int32)
        f = jax.jit(comparable.histogram)
        row(f"T3_histogram_M{m}_N{n}", timeit(f, x, edges), f"steps={m + 1}")


# -- T4: two-phase global sum ----------------------------------------------

def bench_section_sum():
    for n in (4096, 65536, 1048576):
        x = jax.random.normal(jax.random.PRNGKey(0), (n,))
        f = jax.jit(computable.section_sum)
        steps = computable.section_sum_steps(n)
        claim = 2 * int(np.sqrt(n)) + 1
        assert steps <= claim, (steps, claim)
        row(f"T4_section_sum_N{n}", timeit(f, x), f"steps={steps}<=2sqrtN={claim}")
        g = jax.jit(lambda x: computable.section_limit(x, mode="max"))
        row(f"T4_section_max_N{n}", timeit(g, x), f"steps={steps}")


# -- T5: sorting ------------------------------------------------------------

def bench_sort():
    for n in (256, 1024):
        x = jax.random.normal(jax.random.PRNGKey(2), (n,))
        f = jax.jit(computable.odd_even_sort)
        row(f"T5_odd_even_full_N{n}", timeit(f, x, reps=5), f"steps={n}")
        m = computable.optimal_section(n)
        g = jax.jit(lambda x: computable.odd_even_sort(x, m))
        row(f"T5_local_phase_N{n}", timeit(g, x, reps=5), f"steps={m}=sqrtN")
        # disorder left after sqrt(N) local steps (paper: defects spread out)
        after = computable.odd_even_sort(x, m)
        d = int(computable.count_disorder(after))
        row(f"T5_defects_after_sqrtN_N{n}", 0.0, f"defects={d}~N/M={n // m}")


# -- T6: template matching ---------------------------------------------------

def bench_template():
    n = 16384
    data = jax.random.normal(jax.random.PRNGKey(3), (n,))
    for m in (4, 16, 64):
        t = jax.random.normal(jax.random.PRNGKey(4), (m,))
        f = jax.jit(computable.template_match_1d)
        row(f"T6_template_M{m}_N{n}", timeit(f, data, t),
            f"steps={m}(vec)<=paper {m * m}")


# -- T7: line detection ------------------------------------------------------

def bench_line_detect():
    img = jax.random.normal(jax.random.PRNGKey(5), (128, 128))
    for mx, my in ((4, 3), (8, 5)):
        f = jax.jit(lambda im, mx=mx, my=my: computable.line_segment_value(im, mx, my))
        row(f"T7_line_{mx}x{my}", timeit(f, img), f"steps={mx + my}")


# -- T8: collective schedules (R7 ring vs super-connectivity tree) -----------

def bench_collectives():
    script = r"""
import jax, jax.numpy as jnp, time
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec as P
from repro.cpm import collectives
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jnp.ones((8, 4096))
for name, fn in [
    ("ring", lambda v: collectives.ring_allreduce(v, "data")),
    ("tree", lambda v: collectives.tree_allreduce(v, "data")),
    ("psum", lambda v: jax.lax.psum(v, "data"))]:
    f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    for _ in range(50):
        out = f(x)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 50 * 1e6
    steps = {"ring": 7, "tree": 3, "psum": 3}[name]
    print(f"T8_allreduce_{name}_8dev,{us:.1f},steps={steps}")
"""
    run_subbench(script, "T8")


# -- cpm_ops: the CPMArray surface, per backend, against the op table --------

def measured_steps(fn, *args):
    """Concurrent-step count *measured* from the lowered jaxpr.

    Scan trip counts are the sequential concurrent-step structure (each scan
    iteration is one broadcast instruction cycle); everything else in the
    lowering is a constant number of full-array vector ops.  Returns
    ``(scan_steps, loop_free)``.
    """
    closed = jax.make_jaxpr(fn)(*args)
    total, loops = 0, 0

    def walk(jaxpr):
        nonlocal total, loops
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                total += int(eqn.params["length"])
                loops += 1
            elif eqn.primitive.name == "while":
                loops += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)

    walk(closed.jaxpr)
    return total, loops == 0


def bench_cpm_ops():
    """Time every registered op per backend; assert the measured concurrent
    step structure against the formula the op table registers (PR-2)."""
    n, m = 4096, 8
    data = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, 16)
    fdata = data.astype(jnp.float32)
    needle = data[100:100 + m]
    edges = jnp.linspace(0, 16, m + 1).astype(jnp.int32)
    template = fdata[7:7 + m]
    taps = (1.0, 2.0, 1.0)

    calls = {
        "activate": lambda a: a.activate(n // 4, n // 2, 4),
        "shift": lambda a: a.shift(n // 4, n // 2, 1).data,
        "insert": lambda a: a.insert(n // 4, jnp.array([7, 8])).data,
        "delete": lambda a: a.delete(n // 4, 2).data,
        "substring_match": lambda a: a.substring_match(needle),
        "compare": lambda a: a.compare(8, "lt"),
        "histogram": lambda a: a.histogram(edges),
        "section_sum": lambda a: a.section_sum(),
        "global_limit": lambda a: a.global_limit("max"),
        "super_sum": lambda a: a.super_sum(),
        "super_limit": lambda a: a.super_limit("max"),
        "sort": lambda a: a.sort().data,
        "template_match": lambda a: a.template_match(template),
        "stencil": lambda a: a.stencil(taps),
    }
    # reference lowerings whose step structure is a literal scan: the jaxpr
    # trip count must equal the registered formula.  For the §8 super ops
    # (T8: the sqrt(N) -> log N upgrade) the scan trips are the tree levels
    # of both phases, asserted below against the ~2*log2(N)+1 paper bound.
    scan_structured = {"substring_match", "template_match",
                       "super_sum", "super_limit"}
    # ops lowering to a constant number of vector ops: the jaxpr must be
    # loop-free (O(1) concurrent steps regardless of N)
    loop_free = {"activate", "shift", "insert", "delete", "compare",
                 "histogram", "section_sum", "global_limit", "stencil"}

    for op, call in calls.items():
        spec = OP_TABLE[op]
        m_op = len(taps) if op == "stencil" else m
        formula = op_steps(op, n=n, m=m_op)    # bound-checked at evaluation
        for backend in ("reference", "pallas"):
            if backend not in spec.backends:
                continue
            arr = cpm_array((fdata if op in ("template_match", "stencil")
                             else data), n - 7, backend=backend,
                            interpret=(True if backend == "pallas" else None))
            f = jax.jit(lambda a, call=call: call(a))
            us = timeit(f, arr, reps=3 if backend == "pallas" else 20)
            if backend == "reference":
                steps, no_loops = measured_steps(f, arr)
                if op in scan_structured:
                    assert steps == formula, (op, steps, formula)
                elif op in loop_free:
                    assert no_loops, f"{op}: unexpected loop in lowering"
                if op in ("super_sum", "super_limit"):
                    # T8: measured log-depth schedule obeys ~2*log2(N)+1
                    cap = spec.bound(n=n)
                    assert steps <= cap, (op, steps, cap)
            row(f"CPM_{op}_{backend}_N{n}", us,
                f"steps={formula};family={spec.family};paper={spec.paper}")

    # T8 super-connectivity upgrade at the CPMArray surface: jaxpr-measured
    # trip counts of the §8 schedule vs the §7.4 two-phase, across sizes
    for nn in (4096, 65536, 1048576):
        zeros = cpm_array(jnp.zeros(nn, jnp.int32), backend="reference")
        meas, _ = measured_steps(jax.jit(lambda a: a.super_sum()), zeros)
        cap = OP_TABLE["super_sum"].bound(n=nn)
        assert meas == op_steps("super_sum", n=nn), (nn, meas)
        assert meas <= cap, (nn, meas, cap)
        row(f"T8_super_sum_trips_N{nn}", 0.0,
            f"steps={meas}<=2log2N+1={cap};two_phase={op_steps('section_sum', n=nn)}")

    # mesh backend (chips as PEs) for its table entries, on 8 host devices
    script = r"""
import jax, jax.numpy as jnp, time
from repro.cpm import cpm_array
data = jax.random.randint(jax.random.PRNGKey(0), (4096,), 0, 16)
for op, call in [("section_sum", lambda a: a.section_sum()),
                 ("global_limit", lambda a: a.global_limit("max")),
                 ("super_sum", lambda a: a.super_sum()),
                 ("super_limit", lambda a: a.super_limit("max")),
                 ("compare", lambda a: a.compare(8, "lt"))]:
    arr = cpm_array(data, 4089, backend="mesh")
    f = jax.jit(lambda a, call=call: call(a))
    jax.block_until_ready(f(arr))
    t0 = time.perf_counter()
    for _ in range(20):
        out = f(arr)
    jax.block_until_ready(out)
    print(f"CPM_{op}_mesh_N4096,{(time.perf_counter()-t0)/20*1e6:.1f},devices=8")
"""
    run_subbench(script, "CPM_")

    # small-N pallas mitigation (PR 7): measure where pallas actually beats
    # reference for representative ops and record the crossover in the
    # shared tuning cache — ``backends.pallas_min_n`` consults these keys,
    # so ``backend="auto"`` routes tiny arrays to reference (no kernel
    # launch overhead) with a threshold grounded in timings, not folklore.
    # On a CPU container this times interpret kernels (the honest answer is
    # usually "never" — stored as a huge threshold under the interpret
    # backend key); a TPU run writes the compiled-key crossover auto
    # actually reads.
    from repro.cpm import tuning
    from repro.cpm.backends import PALLAS_MIN_N
    sweep = {"compare": lambda a: a.compare(8, "lt"),
             "section_sum": lambda a: a.section_sum()}
    bk = tuning.backend_key(True)
    xovers = []
    for op, call in sweep.items():
        crossover = None
        for nn in (256, 1024, 4096, 16384):
            d = jax.random.randint(jax.random.PRNGKey(2), (nn,), 0, 16)
            f = jax.jit(lambda a, call=call: call(a))
            t_ref = timeit(f, cpm_array(d, backend="reference"), reps=5)
            t_pal = timeit(f, cpm_array(d, backend="pallas",
                                        interpret=True), reps=3)
            if t_pal <= t_ref:
                crossover = nn
                break
        val = crossover if crossover is not None else 1 << 30
        tuning.store(f"xover:{op}:{bk}", int(val))
        xovers.append(val)
        row(f"AT_pallas_crossover_{op}", 0.0,
            f"crossover_n={crossover};static_default={PALLAS_MIN_N};"
            f"key={bk}")
    tuning.store(f"xover:*:{bk}", int(max(xovers)))  # pooled: conservative
    row("AT_pallas_crossover_pooled", 0.0,
        f"min_n={max(xovers)};consulted_by=auto_backend_name")


# -- program_fusion: recorded instruction streams vs eager dispatch (PR 4) ---

def _never_slower(run_sched, run_eager, *args, tries=8, reps=20):
    """Time the cost-aware scheduled path against eager per-op dispatch,
    re-measuring through timer noise (bounded): the cost model's contract
    is that the scheduled structure is never the slower one, so a fair
    re-measurement must find ``speedup_vs_eager >= 1.0`` within ``tries``
    — failing that IS the fusion perf regression this bench gates on."""
    jf, jb = jax.jit(run_sched), jax.jit(run_eager)
    us_f = us_b = float("nan")
    for _ in range(tries):
        us_f = timeit(jf, *args, reps=reps)
        us_b = timeit(jb, *args, reps=reps)
        if us_b >= us_f:
            break
    assert us_b >= us_f, (
        f"scheduled path {us_f:.1f}us slower than eager {us_b:.1f}us "
        f"after {tries} measurements")
    return us_f, us_b


def _decided(plan):
    """The cost model's verdict on the plan's (single) fusable run."""
    g = next(g for g in plan.groups if g.decision is not None)
    return g.kind, g.decision


def bench_program_fusion():
    """The `repro.cpm.program` subsystem: a recorded elementwise/local
    pipeline must lower to strictly fewer pallas_calls than eager per-op
    dispatch when fused (ONE per fused group), stay bit-identical to eager
    reference execution, the op-table cycle model must equal the
    jaxpr-measured trip counts program-wide — and, since the scheduler is
    cost-aware, the *scheduled* path (fused or cost-model fallback to
    per-op dispatch) must never be slower than eager: every
    ``speedup_vs_eager`` row below is asserted >= 1.0x and gated in CI."""
    import os

    from repro.cpm import CPMArray, record, schedule, tuning
    from repro.cpm.program import (FusionGroup, FusionPlan,
                                   count_pallas_calls, program_steps,
                                   scan_structured_steps, scan_trip_count)
    from repro.serve import program_paths

    n = 4096
    data = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, 16)
    vals = jnp.array([7, 8])
    dev = cpm_array(data, n - 7)
    with record() as prog:
        d = dev.shift(2, n // 2, 3)
        d = d.insert(4, vals)
        d.compare(8, "ge")
        d.activate(0, n - 1, 2)
        d.stencil((1.0, 2.0, 1.0))

    def eager_plan(plan):
        """The same instructions, definitionally per-op dispatch."""
        return FusionPlan(plan.program, tuple(
            FusionGroup("eager", g.indices, g.instructions)
            for g in plan.groups))

    # -- launch-structure invariant: forced fuse-all (PR-4 behavior, what
    #    the scheduler emits whenever the cost model predicts fusion wins)
    forced = schedule(prog)

    def run_forced(arr):
        out, outs = forced.run(arr, backend="pallas", interpret=True)
        return out.data, [o for o in outs if o is not None]

    def run_eager(arr):
        d2 = arr.shift(2, n // 2, 3).insert(4, vals)
        return d2.data, [d2.compare(8, "ge"), d2.activate(0, n - 1, 2),
                         d2.stencil((1.0, 2.0, 1.0))]

    pal = cpm_array(data, n - 7, backend="pallas", interpret=True)
    fused_calls = count_pallas_calls(run_forced, pal)
    eager_calls = count_pallas_calls(run_eager, pal)
    assert fused_calls == forced.fused_group_count == 1, fused_calls
    assert fused_calls < eager_calls, (fused_calls, eager_calls)
    row(f"PF_pipeline_pallas_calls_N{n}", 0.0,
        f"fused={fused_calls};eager={eager_calls};"
        f"groups={len(forced.groups)}")

    # bit-identity: forced-fused pallas vs eager reference
    got = run_forced(cpm_array(data, n - 7))
    want = run_eager(cpm_array(data, n - 7, backend="reference"))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # -- the cost-aware scheduled path: never slower than eager (gated).
    #    On this host the calibrated model typically rejects fusion
    #    (interpreter overhead; eager pallas ops jit-fuse for free) — the
    #    forced_fuse_vs_eager figure records what blind fusion would cost.
    plan = schedule(prog, device=pal)
    kind, decision = _decided(plan)

    def run_sched(arr):
        out, outs = plan.run(arr, backend="pallas", interpret=True)
        return out.data, [o for o in outs if o is not None]

    got = run_sched(cpm_array(data, n - 7, backend="pallas", interpret=True))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    us_sched, us_eager = _never_slower(run_sched, run_eager, pal)
    us_forced = timeit(jax.jit(run_forced), pal, reps=5)
    row(f"PF_pipeline_scheduled_N{n}", us_sched,
        f"decision={kind};speedup_vs_eager={us_eager / us_sched:.2f}x;"
        f"predicted_fused_us={decision['fused_us']:.1f};"
        f"predicted_eager_us={decision['eager_us']:.1f};"
        f"params={decision['params']}")
    row(f"PF_pipeline_forced_fuse_N{n}", us_forced,
        f"forced_fuse_vs_eager={us_eager / us_forced:.2f}x;"
        f"eager_us={us_eager:.1f}")

    # -- batched device (8 x 4096): same gate; a forced-fuse run large
    #    enough to engage the fused-stream row-blocking autotuner
    b = 8
    bdata = jax.random.randint(jax.random.PRNGKey(3), (b, n), 0, 16)
    bused = jnp.full((b,), n - 7, jnp.int32) - jnp.arange(b, dtype=jnp.int32)
    bpal = cpm_array(bdata, bused, backend="pallas", interpret=True)
    with record() as bprog:                # programs are device-independent:
        bd = dev.shift(2, n // 2, 3)       # record once, run batched below
        bd.compare(8, "ge")
        bd.stencil((1.0, 2.0, 1.0))
    bplan = schedule(bprog, device=bpal)
    bkind, bdec = _decided(bplan)

    def run_bsched(arr):
        out, outs = bplan.run(arr, backend="pallas", interpret=True)
        return out.data, [o for o in outs if o is not None]

    def run_beager(arr):
        out, outs = eager_plan(bplan).run(arr, backend="pallas",
                                          interpret=True)
        return out.data, [o for o in outs if o is not None]

    bgot = run_bsched(bpal)
    bref, brouts = eager_plan(bplan).run(
        cpm_array(bdata, bused, backend="reference"), backend="reference")
    np.testing.assert_array_equal(np.asarray(bgot[0]), np.asarray(bref.data))
    for g, w in zip(bgot[1], [o for o in brouts if o is not None]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    us_bs, us_be = _never_slower(run_bsched, run_beager, bpal, reps=10)
    row(f"PF_batched_scheduled_b{b}_N{n}", us_bs,
        f"decision={bkind};speedup_vs_eager={us_be / us_bs:.2f}x;"
        f"params={bdec['params']}")

    # forced fuse on the batched device: autotuned block_r vs the default
    # (tuning reads the env at trace time; the winner is a static int).
    # Drop any spilled block_r decisions first so the "default" timing is
    # a real block_r=1 run even when a previous bench populated the cache.
    bforced = schedule(bprog)
    kept = {k: v for k, v in tuning.entries().items()
            if not k.startswith("blockr:")}
    tuning.clear(in_process_only=True)
    for key, val in kept.items():
        tuning.store(key, val)
    prior = os.environ.get("REPRO_CPM_AUTOTUNE")
    os.environ["REPRO_CPM_AUTOTUNE"] = "0"
    try:
        us_default = timeit(
            jax.jit(lambda a: bforced.run(a, backend="pallas",
                                          interpret=True)[0].data),
            bpal, reps=10)
    finally:
        if prior is None:
            os.environ.pop("REPRO_CPM_AUTOTUNE", None)
        else:
            os.environ["REPRO_CPM_AUTOTUNE"] = prior
    us_tuned = timeit(
        jax.jit(lambda a: bforced.run(a, backend="pallas",
                                      interpret=True)[0].data),
        bpal, reps=10)
    blockr = list(tuning.entries("blockr:").values())
    row(f"AT_fused_blockr_b{b}_N{n}", us_tuned,
        f"block_r={blockr[0] if blockr else 1};"
        f"speedup_vs_default={us_default / us_tuned:.2f}x")

    # predicted (op-table sum) vs measured (jaxpr scan trips) cycle counts
    with record() as sprog:
        dev.substring_match(data[100:108])
        dev.template_match(data[7:15].astype(jnp.float32))
        dev.super_sum()
        dev.compare(8, "lt")
    splan = schedule(sprog)
    measured = scan_trip_count(
        lambda a: splan.run(a, backend="reference")[1],
        cpm_array(data, n - 7))
    predicted = scan_structured_steps(sprog, n)
    assert measured == predicted, (measured, predicted)
    row(f"PF_cycles_N{n}", 0.0,
        f"scan_predicted={predicted};scan_measured={measured};"
        f"total_predicted={program_steps(sprog, n)}")

    # the serving hot path: draft-commit, scheduled cost-aware per model
    b, cap, k = 8, 288, 4
    buf = jax.random.randint(jax.random.PRNGKey(1), (b, cap), 0, 1000)
    used = jnp.full((b,), 200, jnp.int32) + jnp.arange(b, dtype=jnp.int32)
    preds = jax.random.randint(jax.random.PRNGKey(2), (b, k), 0, 1000)
    emit = jnp.arange(b, dtype=jnp.int32) % (k + 1)
    calls = count_pallas_calls(
        lambda *a: program_paths.commit_tokens(*a, backend="pallas",
                                               interpret=True),
        buf, used, preds, emit)
    assert calls == 1, calls     # fused OR eager: one launch either way
    rows_idx = jnp.arange(b)

    def legacy_scatter(buf, used, preds, emit):
        tidx = jnp.arange(k)[None]
        widx = jnp.where(tidx < emit[:, None], used[:, None] + tidx, cap)
        return buf.at[rows_idx[:, None], widx].set(preds, mode="drop")

    new_buf, new_used = program_paths.commit_tokens(buf, used, preds, emit)
    leg = np.asarray(legacy_scatter(buf, used, preds, emit))
    for r in range(b):                     # identical within the live region
        np.testing.assert_array_equal(np.asarray(new_buf)[r, :int(new_used[r])],
                                      leg[r, :int(new_used[r])])

    cdev, cplan = program_paths.record_commit_program(
        buf, used, preds, emit, backend="pallas", interpret=True)
    ckind, cdec = _decided(cplan)

    def run_commit(buf, used, preds, emit):
        return program_paths.commit_tokens(buf, used, preds, emit,
                                           backend="pallas",
                                           interpret=True)[0]

    def run_commit_eager(buf, used, preds, emit):
        dev2, p2 = program_paths.record_commit_program(
            buf, used, preds, emit, backend="pallas", interpret=True)
        return eager_plan(p2).run(dev2, backend="pallas",
                                  interpret=True)[0].data

    us_prog, us_ceager = _never_slower(run_commit, run_commit_eager,
                                       buf, used, preds, emit)
    us_leg = timeit(jax.jit(legacy_scatter), buf, used, preds, emit)
    row(f"PF_commit_program_b{b}", us_prog,
        f"decision={ckind};speedup_vs_eager={us_ceager / us_prog:.2f}x;"
        f"pallas_calls=1;legacy_scatter_us={us_leg:.1f}")


# -- LM system benches -------------------------------------------------------

def bench_moe_routing():
    t, e, k = 8192, 32, 8
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (t, e)))
    cpm = jax.jit(lambda p: comparable.topk_mask(p, k))
    ltk = jax.jit(lambda p: jax.lax.top_k(p, k)[1])
    row("MoE_routing_cpm_mask_T8192_E32", timeit(cpm, probs), "steps=2")
    row("MoE_routing_lax_topk_T8192_E32", timeit(ltk, probs), "steps=k")


def bench_lm_smoke():
    from repro.configs import all_configs
    from repro.models import lm
    from repro.train import OptConfig, init_opt_state, make_train_step

    cfg = all_configs()["granite-8b"].smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, OptConfig(), loss_chunk=16))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                          cfg.vocab_size)}

    def f(p, o, b):
        return step(p, o, b)[2]["loss"]

    us = timeit(f, params, opt, batch, reps=5)
    row("LM_train_step_smoke_8x64", us, f"tok_per_s={8 * 64 / (us / 1e6):.0f}")

    caches = lm.init_caches(cfg, 8, max_len=128)
    dstep = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))
    tok = jnp.zeros((8, 1), jnp.int32)
    us = timeit(dstep, params, tok, caches, jnp.asarray(64), reps=10)
    row("LM_decode_step_smoke_b8", us, f"tok_per_s={8 / (us / 1e6):.0f}")


def bench_serve_pool():
    """Continuous batching (paged CPM session pool) vs the static-batch
    engine under a Poisson arrival trace.

    Requests have heterogeneous budgets, so a static batch pins every
    row's pages until its slowest row finishes; the pool retires finished
    rows mid-flight and admits waiting sessions into the freed pages.  At
    >= 2x request oversubscription the pool must win on BOTH occupancy
    and tokens/s (asserted — the PR-5 acceptance criterion), while
    staying token-identical to solo generation (asserted on one session).
    """
    import dataclasses

    from repro.configs import all_configs
    from repro.models import lm
    from repro.serve import Engine, GenConfig

    # bigger-than-smoke model: the decode step must cost enough that slot
    # occupancy (not host dispatch) decides throughput, as it does at
    # production scale
    cfg = dataclasses.replace(all_configs()["granite-8b"].smoke(),
                              d_model=256, n_layers=4, d_ff=512,
                              head_dim=64)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots, s, n_req, chunk = 4, 12, 12, 4
    # heterogeneous budgets: every static batch contains one straggler that
    # pins the batch's pages ~14x longer than its short rows need
    budgets = [58 if i % 4 == 0 else 4 for i in range(n_req)]
    total_tokens = sum(budgets)
    rng = np.random.RandomState(0)
    arrive = np.cumsum(rng.poisson(0.5, n_req))          # ~2 arrivals/step
    arrive[0] = 0
    prompts = [jax.random.randint(jax.random.PRNGKey(100 + i), (s,), 0,
                                  cfg.vocab_size) for i in range(n_req)]
    engine = Engine(cfg, params, max_len=s + max(budgets) + 1)

    def run_static():
        """Batches of ``slots`` in arrival order, each run to completion at
        the batch's max budget (the fixed-batch engine's only option)."""
        emitted = steps = 0
        for i in range(0, n_req, slots):
            bp = jnp.stack(prompts[i:i + slots])
            mx = max(budgets[i:i + slots])
            out, _ = engine.generate({"tokens": bp},
                                     GenConfig(max_new_tokens=mx))
            jax.block_until_ready(out)     # the dispatch is async; a
            # decode-step occupancy accounting (prefill emits each row's
            # first token, so a batch decodes mx - 1 steps)
            emitted += sum(b - 1 for b in budgets[i:i + slots])
            steps += mx - 1
        return emitted, steps

    def run_pool():
        pool = engine.session_pool(slots=slots, chunk=chunk)
        i = 0
        peak_backlog = 0
        while i < n_req or not pool.table.all_done():
            while i < n_req and (arrive[i] <= pool.decode_steps
                                 or pool.table.all_done()):
                pool.submit(prompts[i], budgets[i])
                i += 1
            outstanding = (pool.table.waiting_count()
                           + pool.table.active_count())
            peak_backlog = max(peak_backlog, outstanding)
            pool.step()
        return pool, peak_backlog

    # warm every compile path (prefill shapes, scan, pool step, commits)
    run_static()
    warm_pool, _ = run_pool()

    # token identity spot-check: pooled output == solo static generation
    solo, _ = engine.generate({"tokens": prompts[1][None]},
                              GenConfig(max_new_tokens=budgets[1]))
    np.testing.assert_array_equal(warm_pool.table.get(1).tokens,
                                  np.asarray(solo[0]))

    # wall-clock comparison; one retry absorbs a noisy-neighbor hiccup on
    # shared CI runners (the occupancy comparison below is deterministic
    # step-count math and needs none)
    for attempt in range(2):
        t0 = time.perf_counter()
        emitted, static_steps = run_static()
        static_s = time.perf_counter() - t0
        static_tps = total_tokens / static_s
        static_occ = emitted / (static_steps * slots)

        t0 = time.perf_counter()
        pool, peak_backlog = run_pool()
        pool_s = time.perf_counter() - t0
        pool_tps = total_tokens / pool_s
        stats = pool.stats()
        oversub = peak_backlog / slots
        if pool_tps > static_tps:
            break
        print(f"# serve_pool attempt {attempt}: pool {pool_tps:.1f} <= "
              f"static {static_tps:.1f} tok/s, retrying", file=sys.stderr)

    assert stats["emitted"] == total_tokens, (stats, total_tokens)
    assert oversub >= 2.0, f"trace reached only {oversub:.1f}x oversub"
    assert stats["occupancy"] > static_occ, (stats["occupancy"], static_occ)
    assert pool_tps > static_tps, (pool_tps, static_tps)

    row(f"SP_static_batch_s{slots}", static_s * 1e6,
        f"tok_per_s={static_tps:.1f};occupancy={static_occ:.2f};"
        f"decode_steps={static_steps}")
    row(f"SP_pool_s{slots}", pool_s * 1e6,
        f"tok_per_s={pool_tps:.1f};occupancy={stats['occupancy']:.2f};"
        f"decode_steps={stats['decode_steps']};oversub={oversub:.1f}x")
    row(f"SP_pool_speedup_s{slots}", 0.0,
        f"tps_ratio={pool_tps / static_tps:.2f}x;"
        f"occ_ratio={stats['occupancy'] / static_occ:.2f}x;"
        f"bank_launches={stats['bank_launches']};"
        f"streams_packed={stats['streams_packed']}")

    # -- memory-normalized: paged vs whole-row at FIXED reserved memory ----
    # Both layouts reserve the same KV/token footprint (reserved_tokens
    # logical token-positions).  Whole-row spends it as slots * max_len —
    # capacity bounded by the worst case; paged spends it as sub-pages —
    # capacity bounded by tokens actually resident.  Under a seeded
    # ragged-length burst the paged pool must hold >= 1.5x the concurrent
    # sessions (the ISSUE-8 acceptance gate) while staying token-identical.
    pg, cap_ml = 8, 72
    eng2 = Engine(cfg, params, max_len=cap_ml)
    whole_slots = 4
    reserved_tokens = whole_slots * cap_ml                       # 288
    paged_slots, ppb = 12, reserved_tokens // pg                 # 36 pages
    crng = np.random.RandomState(7)                              # ragged trace
    n_cap = 24
    clens = crng.randint(4, 15, n_cap)
    cbudgets = crng.randint(3, 17, n_cap)
    cprompts = [jax.random.randint(jax.random.PRNGKey(500 + i), (int(s),), 0,
                                   cfg.vocab_size) for i, s in enumerate(clens)]

    def run_capacity(pool):
        sids = [pool.submit(p, int(b)) for p, b in zip(cprompts, cbudgets)]
        peak = resident_sum = ticks = 0
        while not pool.table.all_done():
            pool.step()
            act = pool.table.active()
            peak = max(peak, len(act))
            resident_sum += sum(s.prompt_len + s.emitted for s in act)
            ticks += 1
        return pool.table.outputs(), sids, peak, resident_sum / max(ticks, 1), \
            pool.decode_steps

    whole = eng2.session_pool(slots=whole_slots, chunk=chunk)
    w_out, w_sids, w_peak, w_res, w_steps = run_capacity(whole)
    paged = eng2.session_pool(slots=paged_slots, chunk=chunk, page_size=pg,
                              pages_per_bank=ppb)
    p_out, p_sids, p_peak, p_res, p_steps = run_capacity(paged)

    # identity: the paged layout changes residency, not tokens
    for i in (0, 5, 11):
        solo2, _ = eng2.generate({"tokens": cprompts[i][None]},
                                 GenConfig(max_new_tokens=int(cbudgets[i])))
        np.testing.assert_array_equal(p_out[p_sids[i]], np.asarray(solo2[0]))
        np.testing.assert_array_equal(w_out[w_sids[i]], np.asarray(solo2[0]))

    cap_ratio = p_peak / w_peak
    w_util, p_util = w_res / reserved_tokens, p_res / reserved_tokens
    assert cap_ratio >= 1.5, (
        f"paged capacity at fixed memory only {cap_ratio:.2f}x "
        f"(paged peak {p_peak} vs whole-row peak {w_peak})")
    assert p_util > w_util, (p_util, w_util)

    row(f"SP_wholerow_fixed_mem_{reserved_tokens}tok", 0.0,
        f"peak_sessions={w_peak};tokens_resident_per_reserved="
        f"{w_util:.2f};decode_steps={w_steps}")
    row(f"SP_paged_fixed_mem_{reserved_tokens}tok", 0.0,
        f"peak_sessions={p_peak};tokens_resident_per_reserved="
        f"{p_util:.2f};decode_steps={p_steps};page={pg};pages={ppb}")
    row("SP_paged_capacity_fixed_mem", 0.0,
        f"capacity_ratio={cap_ratio:.2f}x;util_ratio={p_util / w_util:.2f}x;"
        f"steps_ratio={w_steps / p_steps:.2f}x;gate=1.5x")


def bench_serve_gateway():
    """Gateway (batched admission + LRU preemption) vs FIFO-queued
    admission under seeded traffic traces (``benchmarks/traffic.py``).

    Metrics are graded in the pool's virtual decode-step clock, so the
    policy comparison is deterministic: per-request latency (finish -
    arrival), slowdown (latency / the request's ideal solo service time
    ~= its budget), TTFT (arrival -> prefill token), and SLO attainment
    at several deadline scales (deadline = scale * budget + floor — the
    "SLO-graded" axis).  Raw end-to-end p99 latency is reported but NOT
    gated: any work-conserving schedule conserves total service, so
    preemption *redistributes* latency from many short requests to few
    long ones — the win is on p99 slowdown / p99 TTFT / SLO attainment,
    which is exactly the fairness trade the gateway sells.

    Asserted gates (bursty trace at >= 2x oversubscription): the gateway
    beats FIFO on p99 slowdown, p99 TTFT and SLO attainment; batched
    admission pays strictly fewer prefill launches; and one preempted
    request's tokens are byte-identical to solo ``Engine.generate``
    (greedy preemption identity under load).
    """
    import dataclasses

    import traffic

    from repro import obs
    from repro.configs import all_configs
    from repro.models import lm
    from repro.serve import Engine, GenConfig
    from repro.serve.gateway import Gateway, PreemptConfig

    obs.TRACER.clear()                 # scope the exported trace to this bench
    cfg = dataclasses.replace(all_configs()["granite-8b"].smoke(),
                              d_model=128, n_layers=2, d_ff=256)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots, chunk = 4, 2
    bursty = traffic.bursty_trace(incumbents=slots, long_budget=40,
                                  n_bursts=3, burst=8, gap=12, start=4,
                                  seed=0)
    poisson = traffic.poisson_trace(n=24, rate=0.8, seed=1)
    diurnal = traffic.diurnal_trace(n=24, period=24, peak_rate=1.2,
                                    trough_rate=0.1, seed=2)
    traces = {"bursty": bursty, "poisson": poisson, "diurnal": diurnal}
    max_len = max(int(tr.lens.max() + tr.budgets.max())
                  for tr in traces.values()) + 1
    engine = Engine(cfg, params, max_len=max_len)
    SLO_SCALES, SLO_FLOOR = (2.0, 4.0, 8.0), 8

    def prompt(i, s):
        return jax.random.randint(jax.random.PRNGKey(1000 + i), (int(s),),
                                  0, cfg.vocab_size)

    def replay(trace, policy):
        """Drive one gateway through the trace; arrivals are due when the
        pool's decode-step clock reaches them (an idle pool fast-forwards
        to the next arrival — both policies see the identical workload)."""
        gw = Gateway(engine, slots=slots, chunk=chunk,
                     gen=GenConfig(max_new_tokens=4),
                     admit_batching=(policy == "gateway"),
                     preempt=(PreemptConfig() if policy == "gateway"
                              else False))
        rids, i, peak = [], 0, 0
        t0 = time.perf_counter()
        while i < len(trace) or gw.loop.pending():
            while i < len(trace) and (trace.arrivals[i] <= gw.now
                                      or not gw.loop.pending()):
                rids.append(gw.submit(
                    prompt(i, trace.lens[i]), int(trace.budgets[i]),
                    deadline_steps=int(4 * trace.budgets[i] + SLO_FLOOR)))
                i += 1
            st = gw.stats()
            peak = max(peak, st["waiting"] + st["parked"] + st["active"])
            gw.tick()
        wall = time.perf_counter() - t0
        return gw, [gw.request(r) for r in rids], peak, wall

    def metrics(gw, reqs, peak, wall):
        lat = np.array([r.latency_steps for r in reqs], float)
        ttft = np.array([r.ttft_steps for r in reqs], float)
        budgets = np.array([r.budget for r in reqs], float)
        slow = lat / np.maximum(budgets, 1.0)
        return {
            "p50_lat": float(np.percentile(lat, 50)),
            "p99_lat": float(np.percentile(lat, 99)),
            "p99_ttft": float(np.percentile(ttft, 99)),
            "p99_slow": float(np.percentile(slow, 99)),
            "slo": {sc: float(np.mean(lat <= sc * budgets + SLO_FLOOR))
                    for sc in SLO_SCALES},
            "oversub": peak / slots, "wall_s": wall, "stats": gw.stats(),
        }

    replay(bursty, "gateway")                     # warm every compile path
    replay(bursty, "fifo")

    results = {}
    for policy in ("fifo", "gateway"):
        gw, reqs, peak, wall = replay(bursty, policy)
        results[policy] = metrics(gw, reqs, peak, wall)
        if policy == "gateway":
            preempted = [r for r in reqs if r.parks > 0]
            assert preempted, "bursty trace must trigger preemption"
            pick = preempted[0]
            solo, _ = engine.generate(
                {"tokens": jnp.asarray(pick.prompt)[None]},
                GenConfig(max_new_tokens=pick.budget))
            np.testing.assert_array_equal(pick.tokens, np.asarray(solo[0]))

    fifo, gate = results["fifo"], results["gateway"]
    slo_str = lambda m: ";".join(  # noqa: E731
        f"slo@{sc:g}x={m['slo'][sc]:.2f}" for sc in SLO_SCALES)
    for policy, m in results.items():
        st = m["stats"]
        row(f"SG_{policy}_bursty", m["wall_s"] * 1e6,
            f"p50_lat={m['p50_lat']:.0f};p99_lat={m['p99_lat']:.0f};"
            f"p99_ttft={m['p99_ttft']:.0f};p99_slowdown={m['p99_slow']:.2f};"
            f"{slo_str(m)};oversub={m['oversub']:.1f}x;"
            f"occupancy={st['occupancy']:.2f};"
            f"preemptions={st['preemptions']};restores={st['restores']};"
            f"prefill_launches={st['prefill_launches']}")

    # deterministic virtual-time gates: the PR-7 acceptance criterion
    assert gate["oversub"] >= 2.0, gate["oversub"]
    assert gate["p99_slow"] < fifo["p99_slow"], (gate["p99_slow"],
                                                 fifo["p99_slow"])
    assert gate["p99_ttft"] < fifo["p99_ttft"], (gate["p99_ttft"],
                                                 fifo["p99_ttft"])
    assert gate["slo"][4.0] > fifo["slo"][4.0], (gate["slo"], fifo["slo"])
    assert (gate["stats"]["prefill_launches"]
            < fifo["stats"]["prefill_launches"]), "batching saved nothing"
    assert gate["stats"]["preemptions"] > 0
    row("SG_gateway_vs_fifo_bursty", 0.0,
        f"p99_slowdown_ratio={fifo['p99_slow'] / gate['p99_slow']:.2f}x;"
        f"p99_ttft_fifo={fifo['p99_ttft']:.0f};"
        f"p99_ttft_gateway={gate['p99_ttft']:.0f};"
        f"slo4x_fifo={fifo['slo'][4.0]:.2f};"
        f"slo4x_gateway={gate['slo'][4.0]:.2f};"
        f"prefill_launches_saved="
        f"{fifo['stats']['prefill_launches'] - gate['stats']['prefill_launches']}")

    for name in ("poisson", "diurnal"):           # the full SLO grade sweep
        gw, reqs, peak, wall = replay(traces[name], "gateway")
        m = metrics(gw, reqs, peak, wall)
        st = m["stats"]
        row(f"SG_gateway_{name}", m["wall_s"] * 1e6,
            f"p50_lat={m['p50_lat']:.0f};p99_lat={m['p99_lat']:.0f};"
            f"p99_ttft={m['p99_ttft']:.0f};{slo_str(m)};"
            f"oversub={m['oversub']:.1f}x;occupancy={st['occupancy']:.2f};"
            f"preemptions={st['preemptions']};"
            f"admit_batches={st['admit_batches']};"
            f"prefill_launches={st['prefill_launches']}")

    if obs.enabled():
        _serve_gateway_telemetry(cfg, params)


def _serve_gateway_telemetry(cfg, params):
    """PR-9 telemetry artifacts off the serve_gateway replays just run:
    Chrome-trace export (validated: >= 1 span per serving layer),
    Prometheus metrics snapshot, the per-op-family predicted-vs-measured
    cycle-drift table, and the jaxpr-asserted decode-chunk launch-count
    invariance (telemetry on == off)."""
    import os

    from repro import obs
    from repro.cpm import cpm_array, record
    from repro.cpm.program import count_pallas_calls
    from repro.serve import Engine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art = os.path.join(root, "artifacts")
    os.makedirs(art, exist_ok=True)

    # Chrome/Perfetto trace: one span per serving layer, or the export is
    # lying about coverage
    trace = obs.write_trace(os.path.join(art, "OBS_trace.json"))
    counts = obs.validate_chrome_trace(trace)
    layers = ("gateway.tick", "pool.admission", "pool.prefill",
              "pool.decode_chunk", "pool.park", "pool.restore")
    for span_name in layers:
        assert counts.get(span_name, 0) >= 1, (
            f"no {span_name} span in exported trace: {sorted(counts)}")
    obs.write_metrics(os.path.join(art, "OBS_metrics.prom"))
    row("SG_obs_trace", 0.0,
        ";".join(f"{n.rsplit('.', 1)[-1]}={counts[n]}" for n in layers))

    # model-vs-measured cycle drift per op family: audit a representative
    # program (serving-commit ops + one op per budget family) and require
    # zero drift between op-table predictions and jaxpr-measured trips
    dev0 = cpm_array(jnp.arange(64), 48, backend="reference")
    with record() as prog:
        d2 = dev0.insert(3, jnp.array([7, 8]))
        d2 = d2.truncate(48)
        d2.compare(9, "lt")
        d2.substring_match(jnp.array([7, 8]))
        d2.super_sum()
    audit_rows = obs.audit(prog, dev0)
    print(obs.LEDGER.format_drift_table(), flush=True)
    assert all(r["drift"] == 0 for r in audit_rows), audit_rows
    row("SG_obs_cycle_drift", 0.0,
        ";".join(f"{r['family']}.{r['op']}="
                 f"{r['measured_trips']}/{r['predicted_scan']}"
                 for r in audit_rows) + ";max_drift=0")

    # launch-count invariance: building the compiled decode chunk with
    # telemetry on vs off lowers to the identical pallas launch count
    # (recording is host-side between compiled calls — REPRO_OBS can
    # never change what compiles)
    eng = Engine(cfg, params, max_len=32)
    pool = eng.session_pool(slots=2, n_banks=1, chunk=2, page_size=8,
                            pages_per_bank=8, bank_backend="pallas",
                            bank_interpret=True)

    def chunk_launches():
        run = pool._build_chunk(pool.slots, pool.chunk, pool.n_banks,
                                "pallas", True, pool.page_size,
                                pool.pages_per_bank)
        pt = np.full((pool.slots, pool.C), pool.total_pages, np.int32)
        return count_pallas_calls(
            run, eng.params, pool.cur, pool.caches, pool.pos,
            jnp.asarray(pool.live), jnp.zeros((pool.slots,), jnp.int32),
            jnp.asarray(pool._temp), jnp.asarray(pool._topk),
            jnp.asarray(pool._topp), [b.data for b in pool.banks],
            [b.lens for b in pool.banks], jnp.asarray(pt), pool.tok_lens,
            jax.random.PRNGKey(7))

    n_on = chunk_launches()
    saved = os.environ.get("REPRO_OBS")
    os.environ["REPRO_OBS"] = "0"
    try:
        n_off = chunk_launches()
    finally:
        if saved is None:
            os.environ.pop("REPRO_OBS", None)
        else:
            os.environ["REPRO_OBS"] = saved
    assert n_on == n_off == 3 * pool.n_banks, (n_on, n_off)
    row("SG_obs_launch_invariance", 0.0,
        f"pallas_launches_obs_on={n_on};obs_off={n_off};"
        f"expected={3 * pool.n_banks}")


def bench_serve_http():
    """The wire front (PR-10): SSE streaming over ``POST /v1/generate``
    vs the in-process async face, plus the live-observability gates.

    Asserted in-run:

      * **byte-identity** — for every paired request the SSE stream's
        concatenated tokens equal the in-process ``Gateway.stream``
        output as raw bytes (the wire adds framing, never tokens);
      * **TTFT overhead** — mean wall-clock first-token overhead of the
        HTTP/SSE path over the in-process path stays under 100 ms on
        warm paths (generous: CI boxes are noisy; the point is catching
        an accidental sync/buffering stall, not micro-latency);
      * **scrape validity** — a live ``GET /metrics`` parses under the
        strict mini-parser (``repro.obs.promparse``) including histogram
        consistency and derived summary quantiles;
      * **streaming trace** — ``GET /debug/trace`` (chunked) re-validates
        via ``validate_chrome_trace`` with the ring at <= capacity;
      * **burn-rate alerting** — an injected deadline-miss burst fires
        the multi-window monitor and the flight-recorder dump
        round-trips through both validators.
    """
    import asyncio
    import dataclasses
    import json as _json
    import os

    from repro import obs
    from repro.configs import all_configs
    from repro.models import lm
    from repro.obs import promparse
    from repro.obs.slo import BurnWindow, FlightRecorder, SloMonitor
    from repro.serve import Engine, GenConfig, Gateway, HttpFrontend
    from repro.serve import http as wire

    cfg = dataclasses.replace(all_configs()["granite-8b"].smoke(),
                              d_model=128, n_layers=2, d_ff=256)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params, max_len=64)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art = os.path.join(root, "artifacts")
    os.makedirs(art, exist_ok=True)
    n_pairs, budget, plen = 8, 8, 6

    def prompt(i):
        return jax.random.randint(jax.random.PRNGKey(3000 + i), (plen,), 0,
                                  cfg.vocab_size)

    async def ttft_wire(fe, p, deadline=None):
        body = {"prompt": [int(t) for t in np.asarray(p)],
                "max_new_tokens": budget}
        if deadline is not None:
            body["deadline_steps"] = deadline
        toks, first = [], None
        t0 = time.perf_counter()
        async for ev, data in wire.sse_events(fe.host, fe.port,
                                              "/v1/generate", body):
            if ev == "tokens":
                if first is None:
                    first = time.perf_counter() - t0
                toks.extend(_json.loads(data)["tokens"])
        return first, toks

    async def ttft_inproc(gw, p):
        toks, first = [], None
        t0 = time.perf_counter()
        rid = await gw.asubmit(p, budget)
        async for ch in gw.stream(rid):
            if first is None:
                first = time.perf_counter() - t0
            toks.extend(int(t) for t in ch)
        return first, toks

    async def main():
        gw = Gateway(engine, slots=4, n_banks=1, chunk=2,
                     gen=GenConfig(max_new_tokens=budget))
        fe = HttpFrontend(gw, port=0, ring_capacity=2048, keepalive_s=2.0)
        # re-wire the SLO plane with bench-scale windows so the injected
        # burst below trips deterministically
        recorder = FlightRecorder(os.path.join(art, "flightrec"),
                                  ring=fe.ring, pool=gw.pool, last_n=128)
        monitor = SloMonitor(objective=0.9,
                             fast=BurnWindow(steps=16, threshold=4.0),
                             slow=BurnWindow(steps=128, threshold=1.5),
                             recorder=recorder, min_events=4, name="bench")
        gw.slo_monitor = fe.slo_monitor = monitor
        await fe.start()
        await gw.start()
        try:
            # warm every compile path on both faces before timing
            await ttft_wire(fe, prompt(999))
            await ttft_inproc(gw, prompt(998))

            wire_ttft, inproc_ttft, identical = [], [], 0
            for i in range(n_pairs):
                fw, tw = await ttft_wire(fe, prompt(i), deadline=500)
                fi, ti = await ttft_inproc(gw, prompt(i))
                wire_ttft.append(fw)
                inproc_ttft.append(fi)
                identical += (np.asarray(tw, np.int32).tobytes()
                              == np.asarray(ti, np.int32).tobytes())
            assert identical == n_pairs, (
                f"only {identical}/{n_pairs} wire streams byte-identical")
            w_us = np.mean(wire_ttft) * 1e6
            i_us = np.mean(inproc_ttft) * 1e6
            overhead_us = w_us - i_us
            assert overhead_us < 100_000, (
                f"SSE TTFT overhead {overhead_us / 1e3:.1f}ms over "
                f"in-process — the wire front is stalling the stream")
            row(f"HTTP_sse_ttft_n{n_pairs}", w_us,
                f"inproc_us={i_us:.0f};overhead_us={overhead_us:.0f};"
                f"p99_wire_us={np.percentile(wire_ttft, 99) * 1e6:.0f};"
                f"tokens_identical={identical}/{n_pairs};gate=100ms")

            # disconnect-cancel over the wire: the slot must come back
            reader, writer = await asyncio.open_connection(fe.host, fe.port)
            writer.write(wire._request_bytes(
                "POST", "/v1/generate", fe.host,
                _json.dumps({"prompt": [int(t) for t in np.asarray(
                    prompt(997))], "max_new_tokens": 48}).encode()))
            await writer.drain()
            await reader.readuntil(b"start")
            writer.close()
            await writer.wait_closed()
            for _ in range(500):
                if gw.request(gw._next_rid - 1).done:
                    break
                await asyncio.sleep(0.02)
            req = gw.request(gw._next_rid - 1)
            assert req.cancelled, "disconnect did not cancel the request"
            row("HTTP_disconnect_cancel", 0.0,
                f"cancelled=1;tokens_before_cancel="
                f"{len(req.tokens) - plen};free_slots="
                f"{gw.pool.alloc.free_count()}")

            # live /metrics scrape through the strict parser
            st, _, raw = await wire.request(fe.host, fe.port, "GET",
                                            "/metrics")
            assert st == 200
            fams = promparse.parse(raw.decode())
            for fam in ("repro_gateway_requests_total",
                        "repro_http_requests_total",
                        "repro_http_sse_events_total"):
                assert fam in fams, f"scrape missing {fam}"
            n_samples = sum(len(f.samples) for f in fams.values())
            row("HTTP_metrics_scrape", 0.0,
                f"families={len(fams)};samples={n_samples};"
                f"parser=promparse.strict")

            # chunked streaming trace export off the bounded ring
            st, hdrs, raw = await wire.request(fe.host, fe.port, "GET",
                                               "/debug/trace")
            assert st == 200 and hdrs.get("transfer-encoding") == "chunked"
            counts = obs.validate_chrome_trace(_json.loads(raw.decode()))
            rstats = fe.ring.stats()
            assert rstats["len"] <= rstats["capacity"]
            row("HTTP_debug_trace", 0.0,
                f"events={sum(counts.values())};ring_len={rstats['len']};"
                f"ring_capacity={rstats['capacity']};"
                f"ring_dropped={rstats['dropped']};transfer=chunked")

            # injected deadline-miss burst -> burn alert -> flight dump
            for i in range(12):
                st, _, raw = await wire.request(
                    fe.host, fe.port, "POST", "/v1/generate",
                    {"prompt": [int(t) for t in np.asarray(prompt(900 + i))],
                     "max_new_tokens": 4, "deadline_steps": 0,
                     "stream": False})
                assert st == 200
            assert monitor.alerts, "miss burst did not trip the monitor"
            alert = monitor.alerts[0]
            dump_path = alert["dump"]
            assert dump_path and os.path.exists(dump_path)
            dump = _json.load(open(dump_path))
            obs.validate_chrome_trace(dump["trace"])
            promparse.parse(dump["metrics_prom"])
            assert dump["allocator"]["n_slots"] == gw.pool.slots
            row("HTTP_slo_burn_alert", 0.0,
                f"alerts={len(monitor.alerts)};"
                f"fast_burn={alert['fast']['burn']:.1f}x;"
                f"slow_burn={alert['slow']['burn']:.1f}x;"
                f"dump={os.path.basename(dump_path)};"
                f"dump_validators=chrome_trace+promparse")
        finally:
            await gw.stop()
            await fe.stop()

    asyncio.run(main())


def bench_engine_decode():
    """Serving-engine scenarios: scan-decode throughput and batched
    speculative decoding (tokens/sec + draft acceptance rate)."""
    from repro.configs import all_configs
    from repro.models import lm
    from repro.serve import Engine, GenConfig

    cfg = all_configs()["granite-8b"].smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))

    b, s, new = 8, 32, 32
    engine = Engine(cfg, params, max_len=s + new + 8)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                          cfg.vocab_size)}
    gen = GenConfig(max_new_tokens=new)

    def run_scan():
        out, _ = engine.generate(batch, gen)
        return out

    us = timeit(run_scan, reps=5)
    row(f"Engine_scan_decode_b{b}_new{new}", us,
        f"tok_per_s={b * new / (us / 1e6):.0f}")

    # speculative: periodic prompts so the n-gram draft hits often
    bs, ss, draft = 4, 24, 4
    period = jnp.arange(6, dtype=jnp.int32) + 7
    spec_batch = {"tokens": jnp.tile(period[None], (bs, ss // 6))}
    spec_engine = Engine(cfg, params, max_len=ss + new + 4 * draft)
    spec_gen = GenConfig(max_new_tokens=new, ngram_spec=draft)

    def run_spec():
        out, stats = spec_engine.generate(spec_batch, spec_gen)
        return out, stats

    _, stats = run_spec()                                # compile + stats
    us = timeit(lambda: run_spec()[0], reps=5)
    row(f"Engine_spec_decode_b{bs}_draft{draft}", us,
        f"tok_per_s={bs * new / (us / 1e6):.0f};"
        f"accept_rate={stats['acceptance_rate']:.2f};"
        f"rounds={stats['rounds']}")


SCENARIOS = {
    "universal_ops": bench_universal_ops,
    "substring": bench_substring,
    "histogram": bench_histogram,
    "section_sum": bench_section_sum,
    "sort": bench_sort,
    "template": bench_template,
    "line_detect": bench_line_detect,
    "collectives": bench_collectives,
    "cpm_ops": bench_cpm_ops,
    "program_fusion": bench_program_fusion,
    "moe_routing": bench_moe_routing,
    "lm_smoke": bench_lm_smoke,
    "engine_decode": bench_engine_decode,
    "serve_pool": bench_serve_pool,
    "serve_gateway": bench_serve_gateway,
    "serve_http": bench_serve_http,
}


def main(argv=None) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = list(argv if argv is not None else sys.argv[1:])
    json_flag, json_path = False, None
    if "--json" in args:                       # --json [PATH]: machine-
        i = args.index("--json")               # readable copy of the CSV
        json_flag = True                       # rows (the bench trajectory
        nxt = args[i + 1] if i + 1 < len(args) else None   # artifact)
        # a PATH operand must look like one (*.json or contain a path
        # separator) — a typo'd scenario name must NOT silently become an
        # output file while every scenario runs
        if nxt is not None and (nxt.endswith(".json") or "/" in nxt):
            json_path = nxt                    # explicit single output file
            del args[i:i + 2]
        else:                                  # default: one
            del args[i]                        # BENCH_<scenario>.json per
    names = args or list(SCENARIOS)            # scenario at the repo root
    unknown = [s for s in names if s not in SCENARIOS]
    if unknown:
        raise SystemExit(f"unknown scenario(s) {unknown}; have {list(SCENARIOS)}")
    print("name,us_per_call,derived")
    spans = {}
    for s in names:
        start = len(ROWS)
        SCENARIOS[s]()
        spans[s] = (start, len(ROWS))
    if json_flag:
        import json
        import os

        from repro import obs

        def dump(path, rows, scenario):
            # schema v2: rows + the global metrics-registry snapshot, so
            # every BENCH artifact carries the telemetry that produced it
            with open(path, "w") as fh:
                json.dump({
                    "schema_version": 2,
                    "scenario": scenario,
                    "rows": [{"name": n, "us_per_call": us, "derived": d}
                             for n, us, d in rows],
                    "metrics": obs.snapshot(),
                }, fh, indent=1)
            print(f"wrote {len(rows)} rows to {path}", file=sys.stderr)

        if json_path:
            dump(json_path, ROWS, "+".join(names))
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            for s, (a, b) in spans.items():
                dump(os.path.join(root, f"BENCH_{s}.json"), ROWS[a:b], s)


if __name__ == "__main__":
    main()

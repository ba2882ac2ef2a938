#!/usr/bin/env python3
"""Run the serving path once on one TPU chip and check what it serves.

    python chip_smoke.py               # one chip: Engine, SessionPool, Gateway
    python chip_smoke.py --four-chips  # four chips: the repro.cpm mesh backend

One process, which starts no other.  The one-chip run builds granite-8b at
its published widths (d_model 4096, 32 query / 8 KV heads, d_ff 14336,
vocab 49152) with the depth cut to ``LAYERS`` and random weights from
``--seed``, serves a handful of requests through a paged ``Gateway`` whose
token banks and commits run the compiled Pallas kernels, and fails unless

  (a) JAX's platform is ``tpu`` (it never falls back to the CPU);
  (b) the lowered decode chunk holds ``tpu_custom_call``: the Pallas kernels
      run compiled, not interpreted;
  (c) every request returns exactly its budget of token ids in [0, vocab);
  (d) prefill logits through the Pallas attention kernel match the same
      prefill through the jnp reference (``impl="ref"``) on the chip;
  (e) for two served requests, a cache-free full-sequence forward
      (``impl="ref"``) over prompt + served tokens puts every served token
      within a tolerance of that position's largest logit.

``--four-chips`` runs only the multi-chip path: ``compare``,
``section_sum``, ``global_limit`` and ``super_sum`` of the mesh backend over
2**24 int32 split across four chips, compared bit for bit with the
reference backend on one chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
it is printed only when every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# (d): the Pallas kernel and the jnp reference compute the same attention
# but round to bf16 (8 significant bits, a relative step of up to 2**-8)
# at different points: the reference keeps scores and probabilities in
# bf16, the kernel in f32.  Each of the L layers adds that difference to
# the residual stream, and the logits are bf16 themselves, so the
# last-position logits may drift by several bf16 steps of their own scale
# (about 1% at 2 layers and d_model 64 on the CPU): the bound is 5% of the
# largest |logit|.  A wrong mask or head grouping moves them by O(100%).
PREFILL_REL_TOL = 5e-2
# (e): a served token was the argmax of the served logits; the cache-free
# reference logits differ from those by about the (d) drift per entry, so
# the served token may sit below the reference maximum by up to twice
# that drift: 10% of the position's largest |logit|.
SERVED_REL_TOL = 1e-1

# Depth kept of granite-8b's 36 layers.  A compile of the decode chunk at
# 8 layers for a v5e (``compiled.memory_analysis()``) takes 8.250 GiB of
# arguments (8.000 GiB float32 weights, 0.250 GiB paged KV and pool state),
# 0.250 GiB of outputs and 4.192 GiB of temporaries: 12.69 GiB of the
# chip's 15.75 GiB.  Each further layer adds 0.81 GiB of weights and about
# 0.5 GiB of temporaries, so 8 is the deepest cut that keeps ~3 GiB free.
LAYERS = 8

PAGE_SIZE = 16
MAX_LEN = 1024
SLOTS = 8
# (prompt length, budget): lengths above 128 are multiples of 128, as the
# prefill attention kernel's blocks require
REQUESTS = [(128, 32), (512, 64), (128, 48), (512, 32),
            (128, 64), (512, 48), (128, 40), (512, 56)]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def import_repro():
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package next to this script (looked in {src})")
    sys.path.insert(0, src)


def require_tpu(jax) -> dict:
    """The device as JAX reports it; fails off a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU found: JAX runs on {d.platform!r} "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


class CompileClock:
    """Seconds JAX spent in backend compiles, and the number of compile
    events of any kind (tracing, lowering, cache loads), from
    ``jax.monitoring``."""

    def __init__(self, jax):
        self.total = 0.0
        self.events = 0

        def listen(event, duration, **_):
            if "backend_compile" in event:
                self.total += duration
            if "compil" in event:
                self.events += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def prompts(jax, jnp, seed: int, vocab: int):
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(REQUESTS))
    return [jax.random.randint(k, (s,), 0, vocab, jnp.int32)
            for k, (s, _) in zip(keys, REQUESTS)]


def max_logit_gap(jnp, logits, tokens):
    """Per position: (largest logit - logit of ``tokens``) over the
    position's largest |logit|.  logits (S, V) f32, tokens (S,)."""
    top = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return (top - got) / jnp.max(jnp.abs(logits), axis=-1)


def one_chip(cfg, seed: int, jax, jnp) -> None:
    from repro.kernels import ops
    from repro.models import lm
    from repro.serve import Engine, GenConfig
    from repro.serve.gateway import Gateway

    clock = CompileClock(jax)
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(lm.init_params, cfg))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_par = sum(x.size for x in jax.tree.leaves(params))
    say(f"params: {n_par / 1e9:.3f}B float32 on device, "
        f"init {time.perf_counter() - t0:.2f} s")

    # platform defaults throughout: Pallas banks and commits on a TPU
    engine = Engine(cfg, params, max_len=MAX_LEN)
    gw = Gateway(engine, slots=SLOTS, chunk=8, page_size=PAGE_SIZE,
                 gen=GenConfig(max_new_tokens=64))
    pool = gw.pool

    # -- serve ---------------------------------------------------------------
    toks = prompts(jax, jnp, seed, cfg.vocab_size)
    t0 = time.perf_counter()
    rids = [gw.submit(p, budget) for p, (_, budget) in zip(toks, REQUESTS)]
    n_ticks, steady, compiled, live_args = 0, [], 0, None
    while not all(gw.request(r).done for r in rids):
        events = clock.events
        rep = gw.tick()
        n_ticks += 1
        if rep.admitted == 0 and rep.emitted > 0:      # a decode chunk ran
            if clock.events == events:
                steady.append(rep.wall_s)
            else:                        # retirements compile gathers anew
                compiled += 1
        active = pool.table.active()
        if live_args is None and len(active) == SLOTS:
            live_args = pool._chunk_args(active, jax.random.PRNGKey(0))
    serve_s = time.perf_counter() - t0
    outs = [gw.result(r) for r in rids]
    emitted = sum(b for _, b in REQUESTS)
    say(f"served {len(rids)} requests, {emitted} tokens, {n_ticks} "
        f"ticks in {serve_s:.3f} s (compiles included)")
    steady.sort()
    say(f"decode tick wall (no admission, no compile in the tick, chunk 8, "
        f"{SLOTS} slots): "
        + (f"median {steady[len(steady) // 2] * 1e3:.2f} ms over "
           f"{len(steady)} ticks" if steady else "no such tick")
        + f"; {compiled} decode ticks that compiled left out")

    # (b) the decode chunk runs compiled Pallas kernels
    if live_args is None:
        fail(f"no tick had all {SLOTS} slots live")
    run = pool._chunk_program()
    n_kern = run.lower(*live_args).as_text().count("tpu_custom_call")
    say(f"(b) decode chunk: {n_kern} tpu_custom_call sites")
    if n_kern == 0:
        fail("(b) the lowered decode chunk has no tpu_custom_call")
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*live_args))
        times.append(time.perf_counter() - t0)
    say(f"decode chunk alone (8 steps, {SLOTS} live slots, "
        f"block_until_ready): {min(times) * 1e3:.2f} ms best of "
        f"{len(times)}")
    del live_args

    # (c) exact budgets, ids in range
    for (plen, budget), p, out in zip(REQUESTS, toks, outs):
        out = jnp.asarray(out)
        if out.shape != (plen + budget,):
            fail(f"(c) request of prompt {plen}, budget {budget} returned "
                 f"{out.shape[0] - plen} tokens")
        if not bool(jnp.all(out[:plen] == p)):
            fail("(c) a returned prompt differs from the one submitted")
        new = out[plen:]
        if not bool(jnp.all((new >= 0) & (new < cfg.vocab_size))):
            fail(f"(c) token ids outside [0, {cfg.vocab_size})")
    say(f"(c) all {len(rids)} requests returned exactly their budgets")

    # (d) prefill through the Pallas kernel vs the jnp reference
    def prefill_fn():
        return jax.jit(lambda prm, t: lm.prefill(prm, cfg, {"tokens": t},
                                                 max_len=t.shape[1])[0])

    worst_d = 0.0
    for plen in sorted({s for s, _ in REQUESTS}):
        batch = jnp.stack([t for t, (s, _) in zip(toks, REQUESTS)
                           if s == plen])
        pal = prefill_fn()
        jax.block_until_ready(pal(params, batch))          # compile
        t0 = time.perf_counter()
        got = jax.block_until_ready(pal(params, batch))
        dt = time.perf_counter() - t0
        with ops.use_impl("ref"):
            want = jax.block_until_ready(prefill_fn()(params, batch))
        v = cfg.vocab_size               # padded vocab rows hold -1e30
        got = got[..., :v].astype(jnp.float32)
        want = want[..., :v].astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        worst_d = max(worst_d, rel)
        say(f"(d) prefill {batch.shape[0]}x{plen}: {dt * 1e3:.2f} ms on the "
            f"Pallas kernel; max |logit diff| / max |logit| = {rel:.3e} "
            f"(bound {PREFILL_REL_TOL})")
    if worst_d > PREFILL_REL_TOL:
        fail(f"(d) Pallas prefill differs from the reference by {worst_d}")

    # (e) served tokens vs a cache-free full-sequence forward
    def forward_logits(prm, seq):       # weights as arguments, not constants
        x, _ = lm.forward(prm, cfg, {"tokens": seq[None]}, remat=False)
        logits = lm._logits(prm, cfg, x)[0, :, :cfg.vocab_size]
        return logits.astype(jnp.float32)

    worst_e = 0.0
    picks = [0, 1]                 # one 128-token and one 512-token prompt
    for i in picks:
        plen, budget = REQUESTS[i]
        seq = jnp.asarray(outs[i], jnp.int32)
        total = seq.shape[0]
        # the reference kernel's blocks want 512 | S beyond 512; causal
        # attention leaves positions before the padding untouched
        padded = total if total <= 512 else -(-total // 512) * 512
        seq_p = jnp.pad(seq, (0, padded - total))
        with ops.use_impl("ref"):
            logits = jax.jit(forward_logits)(params, seq_p)
        gap = max_logit_gap(jnp, logits[plen - 1:total - 1], seq[plen:])
        g = float(jnp.max(gap))
        worst_e = max(worst_e, g)
        say(f"(e) request {i} (prompt {plen}, {budget} served): largest "
            f"(max logit - served logit) / max |logit| = {g:.3e} "
            f"(bound {SERVED_REL_TOL}); served token is the reference "
            f"argmax at {int(jnp.sum(gap == 0))}/{budget} positions")
    if worst_e > SERVED_REL_TOL:
        fail(f"(e) a served token sits {worst_e} below the reference max")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"compile time: {clock.total:.2f} s in backend compiles")
    say(f"peak device memory: "
        f"{'not reported' if peak is None else f'{peak / 2**30:.3f} GiB'}"
        f" of {stats.get('bytes_limit', 0) / 2**30:.3f} GiB")


def four_chips(jax, jnp) -> None:
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.cpm import cpm_array

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 chips, JAX sees {len(devs)}")
    mesh = jax.make_mesh((4,), ("cpm",), axis_types=(AxisType.Auto,),
                         devices=devs[:4])
    n = 1 << 24
    make = jax.jit(lambda k: jax.random.randint(k, (n,), -1000, 1000,
                                                jnp.int32),
                   out_shardings=NamedSharding(mesh, P("cpm")))
    x = make(jax.random.PRNGKey(0))
    shard_devs = {s.device for s in x.addressable_shards}
    say(f"input: {n} int32 in {len(x.addressable_shards)} shards on "
        f"{len(shard_devs)} distinct devices")
    if len(shard_devs) != 4:
        fail("the mesh input is not split over 4 distinct devices")
    # the user's entry point: a CPMArray on the mesh backend, whose default
    # mesh spans every local chip
    on_mesh = cpm_array(x, backend="mesh")
    on_one = cpm_array(jax.device_put(x, devs[0]), backend="reference")
    cases = {
        "compare": lambda a: a.compare(7, "lt"),
        "section_sum": lambda a: a.section_sum(),
        "global_limit": lambda a: a.global_limit("max"),
        "super_sum": lambda a: a.super_sum(),
    }
    for name, op in cases.items():
        jax.block_until_ready(op(on_mesh))
        t0 = time.perf_counter()
        a = jax.block_until_ready(op(on_mesh))
        dt = time.perf_counter() - t0
        b = jax.block_until_ready(op(on_one))
        same = a.shape == b.shape and a.dtype == b.dtype and bool(
            jnp.all(jax.device_put(a, devs[0]) == b))
        say(f"{name}: mesh {dt * 1e3:.3f} ms; bit-identical to one-chip "
            f"reference: {same}")
        if not same:
            fail(f"{name} on 4 chips differs from the one-chip reference")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh-backend path")
    args = ap.parse_args()

    import_repro()
    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    device = require_tpu(jax)
    say(f"device: {device['kind']} x{device['count']} "
        f"(platform {device['platform']}); compile cache {cache}")

    if args.four_chips:
        four_chips(jax, jnp)
    else:
        from repro.configs import get_config
        base = get_config("granite-8b")
        cfg = dataclasses.replace(base, n_layers=LAYERS)
        say(f"model: granite-8b at published widths (d_model {cfg.d_model},"
            f" {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}); depth cut {base.n_layers} -> "
            f"{cfg.n_layers} layers; weights from seed {args.seed}")
        one_chip(cfg, args.seed, jax, jnp)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

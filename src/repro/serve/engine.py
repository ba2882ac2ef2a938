"""Scan-based batched serving engine with CPM-powered extras.

Decode is a single compiled ``jax.lax.scan`` over fixed-shape state
(current token, KV/recurrent caches, per-row positions, rng): the host
launches ONE XLA program per generate call and syncs once at the end —
zero per-token host round-trips, the serving analogue of the paper's
"compute where the data lives" discipline.

Speculative decoding (prompt-lookup drafts from the paper's
content-searchable memory, §5) works at any batch size:

  * the trailing n-gram of every row is matched against that row's
    generated context concurrently (``searchable.ngram_lookup`` under
    ``vmap`` — ~n concurrent compare steps per the paper);
  * the whole ``draft_len``-token draft is verified in ONE teacher-forced
    forward (``lm.decode_multi``, a scan inside one compiled program);
  * acceptance per row is the searchable carry chain
    (``searchable.verify_draft``);
  * KV rollback after partial acceptance is a vectorized per-row
    ``kv_cache.truncate`` (global attention: O(1) length clamp) plus
    per-row snapshot selection for recurrent states and local-window
    rings (``lm.rollback_caches``).

Rows accept different draft prefixes, so positions and cache lengths are
per-row vectors throughout (``kv_cache.broadcast_lens``).  Rows that
reach their token budget early keep decoding into cache slack until the
slowest row finishes; their extra tokens never reach the output buffer
and never contaminate other rows (all cross-row state is batched
element-wise).  Stats clip the final overshooting round, so
``accepted``/``emitted`` count only tokens actually returned.

Sampling truncation via content-comparable thresholds (sampling.py);
KV management via content-movable ops (kv_cache.py).  The old
step-by-step path lives on as the differential-test oracle in
``reference.py``.

Beyond the static ``generate`` batch, the engine serves a *stream* of
requests through the paged session pool (``session_pool.py``):
``submit``/``step``/``drain`` admit sessions into free KV/token pages
mid-flight, decode one batched step across every live page, and retire
finished sessions so their pages go straight back to the allocator —
continuous batching, token-identical (greedy) to per-session static
generation.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.cpm.reference import searchable
from repro.kernels.cpm_kernels import resolve_backend
from repro.models import lm
from . import kv_cache, program_paths, sampling


@dataclasses.dataclass
class GenConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0           # 0 => greedy
    top_k: int = 0
    top_p: float = 0.0
    ngram_spec: int = 0                # >0: prompt-lookup draft length
    ngram_len: int = 3                 # trailing n-gram matched for drafts

    def _key(self):
        return (self.max_new_tokens, self.temperature, self.top_k,
                self.top_p, self.ngram_spec, self.ngram_len)


class Engine:
    """Batched scan engine (static batch, fixed shapes, one program/call)."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512,
                 jit: bool = True, cpm_backend: str | None = None,
                 cpm_interpret: bool | None = None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._jit = jit
        # backend for the CPM commit path (token-buffer splice):
        # "reference" keeps the one-scatter XLA lowering; "pallas" commits
        # each round through the recorded program as ONE fused_stream
        # mega-kernel launch (see _build_commit).  None: by platform.
        self.cpm_backend = resolve_backend(cpm_backend)
        self.cpm_interpret = cpm_interpret

        def maybe_jit(fn, **kw):
            return jax.jit(fn, **kw) if jit else fn

        self._prefill = maybe_jit(functools.partial(lm.prefill, cfg=cfg),
                                  static_argnames=("max_len",))
        # draft verification: ONE forward over all draft tokens per round
        self._decode_multi = maybe_jit(functools.partial(lm.decode_multi,
                                                         cfg=cfg))
        self._programs: dict = {}
        self._pool = None              # default continuous-batching pool

    # -- public API --------------------------------------------------------

    def generate(self, batch: dict, gen: GenConfig, rng=None):
        """Returns (tokens (B, prompt+new), stats).

        stats: ``accepted`` / ``proposed`` draft-token counts (clipped to
        the token budget), ``emitted`` total new tokens, ``rounds``
        speculative rounds, ``acceptance_rate`` = accepted/proposed.
        """
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        tokens = jnp.asarray(batch["tokens"], jnp.int32)
        b, s = tokens.shape
        if gen.max_new_tokens <= 0:
            return tokens, {"accepted": 0, "proposed": 0, "rounds": 0,
                            "emitted": 0, "acceptance_rate": 0.0}
        logits, caches = self._prefill(self.params, batch=batch,
                                       max_len=self.max_len)
        caches = kv_cache.broadcast_lens(caches, b)
        pos = jnp.full((b,), s, jnp.int32)
        spec = (gen.ngram_spec > 0 and gen.temperature <= 0
                and s >= min(gen.ngram_len, s - 1) + 2)
        if spec:
            out, stats = self._generate_spec(tokens, logits, caches, pos, gen)
        else:
            out, stats = self._generate_scan(tokens, logits, caches, pos,
                                             gen, rng)
        prop = stats["proposed"]
        stats["acceptance_rate"] = stats["accepted"] / prop if prop else 0.0
        return out[:, : s + gen.max_new_tokens], stats

    def _sample(self, logits, gen: GenConfig, rng):
        return sampling.sample(logits, rng, gen.temperature, gen.top_k,
                               gen.top_p)

    # -- non-speculative: one scan program, zero per-token syncs -----------

    def _generate_scan(self, tokens, logits, caches, pos, gen: GenConfig,
                       rng):
        b, s = tokens.shape
        run = self._program("scan", gen, self._build_scan, gen)
        seq, _, _ = run(self.params, logits, caches, pos, rng)
        out = jnp.concatenate([tokens, seq], axis=1)
        return out, {"accepted": 0, "proposed": 0, "rounds": 0,
                     "emitted": b * gen.max_new_tokens}

    def _build_scan(self, gen: GenConfig):
        steps = gen.max_new_tokens
        cfg = self.cfg

        def run(params, logits0, caches, pos, rng):
            first = self._sample(logits0[:, -1], gen, rng)

            def body(carry, _):
                tok, caches, pos, rng = carry
                rng, sub = jax.random.split(rng)
                logits, caches = lm.decode_step(params, cfg, tok[:, None],
                                                caches, pos)
                nxt = self._sample(logits[:, -1], gen, sub)
                return (nxt, caches, pos + 1, rng), nxt

            (_, caches, pos, _), toks = jax.lax.scan(
                body, (first, caches, pos, rng), None, length=steps - 1)
            seq = jnp.concatenate([first[:, None], jnp.moveaxis(toks, 0, 1)],
                                  axis=1)
            return seq, caches, pos

        return jax.jit(run) if self._jit else run

    # -- batched prompt-lookup speculative decoding ------------------------

    def _generate_spec(self, tokens, logits, caches, pos, gen: GenConfig):
        b, s = tokens.shape
        max_new = gen.max_new_tokens
        # an active row's last verify round can write up to draft_len - 1
        # KV slots past its budget; without this slack the global-attn
        # slot write (pos % slots) would wrap onto live prompt KV
        need = s + max_new + gen.ngram_spec - 1
        if self.max_len < need:
            raise ValueError(
                f"speculative decoding needs max_len >= prompt + "
                f"max_new_tokens + ngram_spec - 1 = {need}, got "
                f"{self.max_len}")
        buf = jnp.zeros((b, s + max_new), jnp.int32).at[:, :s].set(tokens)
        buf = buf.at[:, s].set(sampling.greedy(logits[:, -1]))
        n_new = jnp.ones((b,), jnp.int32)
        stats = {"accepted": 0, "proposed": 0, "rounds": 0, "emitted": b}

        draft_prog = self._program("draft", gen, self._build_draft, s, gen)
        commit_prog = self._program("commit", gen, self._build_commit,
                                    s, gen)
        while int(jnp.min(n_new)) < max_new:             # one sync per round
            seq, draft = draft_prog(buf, n_new)
            logits, caches, snaps = self._decode_multi(
                self.params, tokens=seq, caches=caches, pos=pos)
            buf, n_new, caches, pos, acc, prop, emit = commit_prog(
                buf, n_new, caches, snaps, draft, logits, pos)
            stats["accepted"] += int(acc)
            stats["proposed"] += int(prop)
            stats["emitted"] += int(emit)
            stats["rounds"] += 1
        return buf, stats

    def _build_draft(self, s: int, gen: GenConfig):
        """(buf, n_new) -> (seq (B,T) verification input, draft (B,T))."""
        draft_len = gen.ngram_spec
        n = min(gen.ngram_len, s - 1)

        def run(buf, n_new):
            b, cap = buf.shape
            rows = jnp.arange(b)
            total = s + n_new                            # (B,) live lengths
            # trailing n-gram per row
            gidx = total[:, None] - n + jnp.arange(n)[None]
            ngram = buf[rows[:, None], gidx]
            # search context = live tokens minus the final one (the trailing
            # self-match must not count); dead slots get -1, matching nothing
            live = jnp.arange(cap)[None] < (total - 1)[:, None]
            ctx = jnp.where(live, buf, -1)
            starts, valid = jax.vmap(
                functools.partial(searchable.ngram_lookup, max_out=1))(
                    ctx, ngram)
            start, ok = starts[:, 0], valid[:, 0]
            # draft = continuation after the earliest historical occurrence,
            # zero-padded past the live region (degenerate rows draft zeros)
            didx = start[:, None] + jnp.arange(draft_len)[None]
            vals = buf[rows[:, None], jnp.minimum(didx, cap - 1)]
            draft = jnp.where(ok[:, None] & (didx < total[:, None]), vals, 0)
            last = buf[rows, total - 1]
            seq = jnp.concatenate([last[:, None], draft[:, :-1]], axis=1)
            return seq, draft

        return jax.jit(run) if self._jit else run

    def _build_commit(self, s: int, gen: GenConfig):
        """Acceptance, rollback, and output-buffer commit for one round.

        The paper-side sequence — draft verify (§5 carry chain) -> KV
        rollback (§4.2 truncate) -> token splice (§4.2 insert) — commits
        through a CPM program (``serve.program_paths``) on the pallas/mesh
        backends: the insert+truncate pair on the token buffer is one
        fusion group, so a commit round on pallas is a single mega-kernel
        launch instead of per-op dispatch.  On the default reference
        backend the same splice stays a one-scatter XLA op (no launches to
        fuse, and the scatter touches only draft_len slots).  Both paths
        are token-identical within the returned live region
        (``tests/test_program.py`` asserts engine-output equality).
        """
        draft_len, max_new = gen.ngram_spec, gen.max_new_tokens
        cfg = self.cfg

        def run(buf, n_new, caches, snaps, draft, logits, pos):
            preds = sampling.greedy(logits)              # (B, T) greedy
            n_acc = searchable.verify_draft(draft, preds)         # (B,)
            n_emit = jnp.minimum(n_acc + 1, draft_len)   # always >= 1
            # rollback: snapshots for recurrent/ring state, then the
            # vectorized per-row length truncation for global-attn KV
            caches = lm.rollback_caches(cfg, caches, snaps, n_emit - 1)
            new_pos = pos + n_emit
            caches = kv_cache.truncate(caches, new_pos)
            # commit emitted tokens (= preds over the kept prefix) at
            # per-row offsets; rows past their budget write nothing that
            # the returned live region can see
            remaining = jnp.maximum(max_new - n_new, 0)
            emit_n = jnp.minimum(n_emit, remaining)
            if self.cpm_backend == "reference":
                # XLA-native realization of the same §4.2 splice: one
                # scatter touching draft_len slots.  The recorded program
                # rolls whole rows — equivalent within the live region but
                # ~10x the vector work (bench PF_commit_program_b8), and
                # its fusion win only exists where launches cost something.
                b, cap = buf.shape
                rows = jnp.arange(b)
                tidx = jnp.arange(draft_len)[None]
                widx = jnp.where(tidx < emit_n[:, None],
                                 s + n_new[:, None] + tidx, cap)
                buf = buf.at[rows[:, None], widx].set(preds, mode="drop")
                n_new = n_new + emit_n
            else:
                buf, new_used = program_paths.commit_tokens(
                    buf, s + n_new, preds, emit_n,
                    backend=self.cpm_backend, interpret=self.cpm_interpret)
                n_new = new_used - s
            acc = jnp.sum(jnp.minimum(n_acc, emit_n))
            # proposed, like accepted, counts only draft tokens within the
            # budget, so acceptance_rate reflects returned tokens
            prop = jnp.sum(jnp.minimum(draft_len, remaining))
            return buf, n_new, caches, new_pos, acc, prop, jnp.sum(emit_n)

        return jax.jit(run) if self._jit else run

    # -- continuous batching (paged session pool) --------------------------

    def session_pool(self, slots: int = 8, n_banks: int = 1, gen=None,
                     **kw):
        """A fresh continuous-batching pool over this engine's weights:
        ``slots`` KV/token pages split across ``n_banks`` CPM banks (see
        ``repro.serve.session_pool``).  Compiled programs are shared
        through this engine's cache, so pools are cheap to recreate."""
        from .session_pool import SessionPool
        return SessionPool(self, slots=slots, n_banks=n_banks, gen=gen,
                           **kw)

    def submit(self, tokens, max_new_tokens: int | None = None, **pool_kw):
        """Queue one request on the engine's default session pool (created
        on first use; ``pool_kw`` configures that first creation).
        Returns the session id — ``step()``/``drain()`` advance it."""
        if getattr(self, "_pool", None) is None:
            self._pool = self.session_pool(**pool_kw)
        elif pool_kw:
            raise ValueError("default pool already exists; use "
                             "session_pool() for a differently-shaped one")
        return self._pool.submit(tokens, max_new_tokens)

    def step(self):
        """One continuous-batching step on the default pool: admit waiting
        sessions into free pages, decode one token per live page, retire
        finished sessions.  Returns the pool's stats snapshot."""
        if getattr(self, "_pool", None) is None:
            raise RuntimeError("no sessions submitted")
        return self._pool.step()

    def drain(self):
        """Run the default pool to completion; returns
        ``{session_id: (prompt + generated,) tokens}``."""
        if getattr(self, "_pool", None) is None:
            raise RuntimeError("no sessions submitted")
        out = self._pool.drain()
        return out

    # -- compiled-program cache -------------------------------------------

    def _program(self, name, gen: GenConfig, builder, *args):
        """Compiled-program cache.

        Builders close over *static* shape parameters (prompt length, pool
        row count) that ``jax.jit`` cannot recover by retracing, so the
        cache key must cover them: it is ``(name, GenConfig key, static
        builder args)``.  Keying on the name alone collided as soon as the
        session pool drove varying shapes through one engine — two pools
        (or two prompt lengths) sharing a name must compile separately.
        GenConfig args contribute via ``_key()``; other non-hashable args
        are rejected rather than silently collapsed into one cache line.
        """
        def static(a):
            if isinstance(a, GenConfig):
                return a._key()
            if isinstance(a, (int, float, str, bool, tuple, frozenset,
                              type(None))):
                return a
            raise TypeError(
                f"_program builder arg {a!r} is not statically hashable; "
                f"pass dynamic values to the compiled function, not the "
                f"builder")

        key = (name, gen._key() if gen is not None else None,
               tuple(static(a) for a in args))
        if key not in self._programs:
            self._programs[key] = builder(*args)
        return self._programs[key]

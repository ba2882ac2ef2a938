"""Continuous batching over paged CPM banks.

The static engine runs one batch to completion: a single slow request pins
every row's VMEM/HBM for the whole generation.  The session pool replaces
that with the paper's facility view of memory (§4.2): a fixed set of
fixed-size **sub-pages** — KV-cache pages and token-buffer bank pages —
that sessions check in and out of mid-flight:

  * ``submit``  — queue a prompt + token budget (FIFO), optionally with
    per-request sampling params (a GenConfig override);
  * ``step``    — admit waiting sessions into free pages with **batched
    admission** (same-length prompts bucket into ONE stacked prefill
    launch + ONE scatter program, so admission cost scales with arrival
    batches, not arrivals; parked sessions restore in one group, no
    prefill), decode a ``chunk`` of tokens for every session in ONE
    compiled program (an inner scan with per-row positions) that reads
    and commits KV **through the page table**, then retire finished
    sessions and reclaim their pages;
  * ``park``    — preempt an ACTIVE session: only its LIVE sub-pages are
    saved to a host-side :class:`PageState` parking buffer, the slot and
    page list are freed, and the session re-queues FIFO for a later
    restore that continues the token stream exactly where it was cut
    (the LRU *policy* lives in ``repro.serve.gateway.preempt``; this is
    the mechanism);
  * ``cancel``  — abort a session in any phase, returning what ran;
  * ``drain``   — step until every submitted session is done.

Paged layout (the vLLM idea expressed as CPM ops): storage is
``page_size``-token sub-pages, not ``max_len`` rows.  Each session holds
an ordered *page list* (``SlotAllocator.pages``); a per-slot page table
``(slots, C)`` maps logical page ranks to sub-page ids.  Global-attn KV
leaves live as page pools (``kv_cache.paged_pool``), token rows as
``(pages_per_bank, page_size)`` banks.  The compiled chunk gathers each
session's FULL logical row through the table (bit-identical attention —
same width, same mask as the un-paged layout), scans ``chunk`` decode
steps, then scatters back only the *dirty* pages (ranks touched since
the chunk started; clean pages keep their sentinel and drop).  Sessions
are admitted with ``ceil((prompt+1)/page_size)`` pages and topped up
host-side between chunks (``_ensure_pages``, one page grant per bank)
with enough slack to cover the next chunk — a session crossing a page
boundary mid-decode never stalls the compiled step.  When a bank runs
dry the youngest sessions park (their pages free instantly), so the
oldest always progresses and a lone session can never livelock.

Bookkeeping is CPM all the way down: free-slot and free-page lookups run
on the allocator's metadata devices (§6 ``compare`` + Rule-6 drain,
§7.5 ``global_limit(min)`` for the LRU victim), token commits are §4.2
``insert``/``truncate`` instruction streams over the gathered logical
rows, and sub-pages move through the scalar-prefetch gather/scatter
kernels on pallas banks.  The host keeps only mirrors (live flags,
budgets, page lists) — a steady-state step is one compiled call, no
device round-trips.

Correctness contract: under greedy decoding the pool is **token-identical**
to generating each session alone with ``Engine.generate`` — decode math is
row-independent, admission replays the same per-session prefill, the paged
gather/scatter round-trip is a pure copy, and each session sees exactly
the same (token, position, cache) sequence it would see solo, at any
``chunk`` size (a session finishing mid-chunk keeps decoding into slack
like the static engine's overshoot rows; the commit clamps to its budget
so overshoot tokens never surface).  The identity survives preemption:
``(live sub-pages, pos, cur, token row)`` fully determine a session's
future, so a parked page image restored into *any* free slot + page list
replays the same stream — ``tests/test_session_pool.py`` and
``tests/test_gateway.py`` assert both differentially.  Sampled decoding
is supported (per-request sampling params via
:func:`repro.serve.sampling.sample_rows`, per-step rng) but makes no
cross-engine identity claim — the rng schedule differs.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.cpm.pool import CPMBank, MultiBankScheduler, SessionTable, SlotAllocator
from repro.cpm.pool.sessions import ACTIVE, DONE, PARKED
from repro.kernels.cpm_kernels import resolve_backend
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from . import kv_cache, sampling

# -- registry-backed accounting ---------------------------------------------
# Each pool instance is one label (pool="<id>") on these shared families;
# the pool's legacy counter attributes (``pool.prefill_launches`` etc.) are
# ``series_property`` views over its series, so ``stats()`` and the
# telemetry exports read the very same cells.  All host arithmetic —
# nothing here ever touches a device array (the PR-6 trace-safety rule).
_POOL_IDS = itertools.count()

_POOL_COUNTERS = {
    "decode_steps": ("repro_pool_decode_steps_total",
                     "virtual decode-step clock (chunks x chunk size)"),
    "total_emitted": ("repro_pool_emitted_total",
                      "tokens emitted (prefill + decode)"),
    "_decode_emitted": ("repro_pool_decode_emitted_total",
                        "budgeted decode tokens (excludes prefill)"),
    "submitted": ("repro_pool_submitted_total", "sessions submitted"),
    "admits": ("repro_pool_admits_total",
               "fresh sessions admitted (restores counted separately)"),
    "prefill_launches": ("repro_pool_prefill_launches_total",
                         "stacked prefill launches"),
    "admit_batches": ("repro_pool_admit_batches_total",
                      "same-length admission buckets executed"),
    "preemptions": ("repro_pool_preemptions_total", "sessions parked"),
    "page_stalls": ("repro_pool_page_stalls_total",
                    "parks forced by page pressure"),
    "restores": ("repro_pool_restores_total", "parked sessions restored"),
    "cancels": ("repro_pool_cancels_total", "sessions cancelled"),
}
_POOL_GAUGES = {
    "active": ("repro_pool_active", "sessions decoding this step"),
    "waiting": ("repro_pool_waiting", "fresh sessions queued"),
    "parked": ("repro_pool_parked", "preempted sessions queued"),
    "pages_free": ("repro_pool_pages_free", "free sub-pages, all banks"),
    "occupancy": ("repro_pool_occupancy",
                  "budgeted decode tokens per slot-step"),
}
_POOL_FAMILIES = (
    {k: obs_metrics.counter(name, help, ("pool",))
     for k, (name, help) in _POOL_COUNTERS.items()}
    | {k: obs_metrics.gauge(name, help, ("pool",))
       for k, (name, help) in _POOL_GAUGES.items()}
)


@dataclasses.dataclass
class PageState:
    """Host-side parking image of one preempted session: everything the
    pooled decode needs to continue token-identically from any free slot
    — its LIVE KV sub-pages flattened to a logical ``n_pages *
    page_size`` row per global-attn leaf (per-slot leaves — rings,
    recurrent states, lengths — ride along in the same trees), the scan
    position, the current token, and its token row."""
    caches: Any                        # {"blocks": [...], "tail": [...]} np
    pos: int
    cur: int
    row: np.ndarray                    # (row_len,) token content
    row_len: int
    n_pages: int                       # live sub-pages saved per leaf


class SessionPool:
    """Paged continuous-batching state for one :class:`~repro.serve.Engine`.

    ``slots`` sessions are split across ``n_banks`` equal banks (the model
    batch is the concatenation of all banks' rows).  ``page_size`` sets
    the sub-page width in tokens (default: ``max_len`` — one page per
    session, the degenerate whole-row layout); ``pages_per_bank`` sets
    each bank's sub-page pool size (default: enough for every slot's
    worst case, i.e. whole-row capacity).  A *paged* pool uses
    ``page_size < max_len`` with ``pages_per_bank`` well below the worst
    case — capacity is then bounded by tokens actually resident, not by
    ``slots * max_len``.  ``gen`` fixes the pool-wide sampling
    parameters; per-session budgets come from ``submit``.  ``chunk``
    tokens decode per ``step`` inside one compiled program — larger
    chunks amortize dispatch, at the cost of coarser
    admission/retirement granularity.  ``bank_backend``/``bank_interpret``
    route the token banks ("pallas" turns each chunk's bank commit into
    one fused mega-kernel launch and sub-page moves into scalar-prefetch
    DMA kernels; the default picks by platform, "pallas" on a TPU).
    ``admit_batching=False`` degrades admission to strict one-at-a-time
    FIFO (buckets of one) — the baseline policy the ``serve_gateway``
    benchmark compares against.
    """

    # legacy counter attributes, now thin views over the pool's registry
    # series (``self._obs_series``) — ``pool.prefill_launches += 1`` keeps
    # working and the metrics exports see the same numbers
    decode_steps = obs_metrics.series_property("decode_steps")
    total_emitted = obs_metrics.series_property("total_emitted")
    _decode_emitted = obs_metrics.series_property("_decode_emitted")
    submitted = obs_metrics.series_property("submitted")
    admits = obs_metrics.series_property("admits")
    prefill_launches = obs_metrics.series_property("prefill_launches")
    admit_batches = obs_metrics.series_property("admit_batches")
    preemptions = obs_metrics.series_property("preemptions")
    page_stalls = obs_metrics.series_property("page_stalls")
    restores = obs_metrics.series_property("restores")
    cancels = obs_metrics.series_property("cancels")

    def __init__(self, engine, slots: int = 8, n_banks: int = 1, gen=None,
                 chunk: int = 1, bank_backend: str | None = None,
                 bank_interpret: bool | None = None, rng=None,
                 admit_batching: bool = True, page_size: int | None = None,
                 pages_per_bank: int | None = None):
        from .engine import GenConfig

        if engine.cfg.enc_dec:
            raise NotImplementedError(
                "session pool supports decoder-only models (cross-attention "
                "pages are encoder-owned)")
        if slots <= 0 or n_banks <= 0 or slots % n_banks:
            raise ValueError(f"slots ({slots}) must be a positive multiple "
                             f"of n_banks ({n_banks})")
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        self.engine = engine
        self.gen = gen if gen is not None else GenConfig()
        self.slots = slots
        self.n_banks = n_banks
        self.rows_per_bank = slots // n_banks
        self.chunk = chunk
        self.max_len = engine.max_len
        bank_backend = resolve_backend(bank_backend)
        self._bank_backend = bank_backend
        self._bank_interpret = bank_interpret

        pg = self.max_len if page_size is None else page_size
        if not 0 < pg <= self.max_len or self.max_len % pg:
            raise ValueError(
                f"page_size ({pg}) must be a positive divisor of max_len "
                f"({self.max_len})")
        self.page_size = pg
        self.C = self.max_len // pg        # page-table width per slot
        ppb = (self.rows_per_bank * self.C if pages_per_bank is None
               else pages_per_bank)
        if ppb <= 0:
            raise ValueError(f"pages_per_bank must be positive, got {ppb}")
        self.pages_per_bank = ppb
        self.total_pages = n_banks * ppb   # doubles as the table sentinel

        self.alloc = SlotAllocator(slots, n_pages=self.total_pages)
        self.banks = [CPMBank(ppb, pg, backend=bank_backend,
                              interpret=bank_interpret)
                      for _ in range(n_banks)]
        self.sched = MultiBankScheduler(self.banks)
        self.table = SessionTable()

        caches = lm.init_caches(engine.cfg, slots, self.max_len)
        caches = kv_cache.broadcast_lens(caches, slots)
        self.caches = kv_cache.paged_pool(caches, engine.cfg,
                                          self.total_pages, pg)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.cur = jnp.zeros((slots,), jnp.int32)
        self.tok_lens = jnp.zeros((slots,), jnp.int32)
        self.live = np.zeros((slots,), bool)
        self._free_hint = slots            # host mirror of the free count
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.admit_batching = admit_batching

        # host mirrors of each slot's sampling params (per-request
        # GenConfig overrides realized as (slots,) vectors for the chunk)
        self._temp = np.full((slots,), self.gen.temperature, np.float32)
        self._topk = np.full((slots,), self.gen.top_k, np.int32)
        self._topp = np.full((slots,), self.gen.top_p, np.float32)

        # per-pool telemetry series: the counter attributes declared on the
        # class read/write these cells (fresh label -> fresh zeroed series)
        self._pool_label = str(next(_POOL_IDS))
        self._obs_series = {k: fam.labels(pool=self._pool_label)
                            for k, fam in _POOL_FAMILIES.items()}

    # -- paging arithmetic --------------------------------------------------
    def pages_for(self, tokens: int) -> int:
        """Sub-pages needed to hold ``tokens`` of content."""
        return -(-tokens // self.page_size)

    def _bank_of(self, slot: int) -> int:
        return slot // self.rows_per_bank

    def _page_range(self, bank: int) -> tuple[int, int]:
        """Bank ``bank``'s slice of the global sub-page id space."""
        return bank * self.pages_per_bank, (bank + 1) * self.pages_per_bank

    def _grant0(self, prompt_len: int) -> int:
        """Admission grant: pages covering the prompt + its prefill token."""
        return min(self.C, self.pages_for(prompt_len + 1))

    # -- public API ---------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int | None = None,
               gen=None) -> int:
        """Queue one session; returns its id.

        ``gen`` optionally overrides the pool GenConfig's *sampling*
        params (temperature/top_k/top_p) for this session — the serving
        gateway's per-request knobs.  The budget comes from
        ``max_new_tokens``, falling back to the per-request then the pool
        GenConfig.  Degenerate requests are rejected here, before they
        can occupy a page: empty prompts, non-positive budgets, requests
        longer than a logical row, and requests whose worst-case page
        count exceeds one bank's capacity all raise ``ValueError``.
        """
        tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
        s = int(tokens.shape[0])
        if s < 1:
            raise ValueError(
                "empty prompt: a session needs at least one prompt token")
        g = self.gen if gen is None else gen
        if gen is not None and getattr(gen, "ngram_spec", 0):
            raise ValueError(
                "pooled serving is non-speculative: per-request "
                "ngram_spec is not supported")
        budget = g.max_new_tokens if max_new_tokens is None else max_new_tokens
        if budget <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got {budget}: a "
                "session must generate at least one token")
        if s + budget > self.max_len:
            raise ValueError(
                f"prompt ({s}) + budget ({budget}) exceeds max_len "
                f"({self.max_len}); pages are max_len wide")
        worst = min(self.C,
                    self.pages_for(s + budget - 1 + self.chunk))
        if worst > self.pages_per_bank:
            raise ValueError(
                f"prompt ({s}) + budget ({budget}) needs up to {worst} "
                f"sub-pages of {self.page_size} tokens, but bank capacity "
                f"is {self.pages_per_bank} pages — the session could "
                f"never be seated")
        sess = self.table.add(tokens, s, budget)
        sess.gen = g
        self.submitted += 1
        return sess.sid

    def _vclock(self) -> int:
        """The pool's virtual clock for spans: decode steps elapsed."""
        return self.decode_steps

    def step(self) -> dict:
        """Admit -> decode ``chunk`` tokens for every live session ->
        retire.  Returns a stats snapshot (see :meth:`stats`)."""
        self._admit()
        self._retire()                      # budget-1 sessions finish on admit
        if self.table.active_count():
            self._ensure_pages()            # slack for the next chunk
        if self.table.active_count():
            self._decode_chunk()
            self._retire()
        return self.stats()

    def drain(self) -> dict[int, np.ndarray]:
        """Step until every submitted session is DONE; returns
        ``{sid: (prompt + generated,) int32}`` for the sessions finished
        since the last drain (delivered sessions are evicted from the
        table — memory stays bounded under a continuous request stream)."""
        while not self.table.all_done():
            self.step()
        return self.table.collect_finished()

    def stats(self) -> dict:
        steps = self.decode_steps
        st = {
            "decode_steps": steps,
            "emitted": self.total_emitted,
            # useful (budgeted) *decode* tokens per slot-step — dead rows,
            # chunk overshoot and drained-out tails all count against it
            # (prefill tokens are excluded: they cost no decode step)
            "occupancy": (self._decode_emitted / (steps * self.slots)
                          if steps else 0.0),
            "active": self.table.active_count(),
            # fresh arrivals only; parked sessions are queued but counted
            # separately (they already hold generated state)
            "waiting": (self.table.waiting_count()
                        - self.table.parked_count()),
            "parked": self.table.parked_count(),
            "pages_free": self.alloc.page_free_count(),
            "bank_launches": self.sched.bank_launches,
            "streams_packed": self.sched.streams_packed,
            "prefill_launches": self.prefill_launches,
            "admit_batches": self.admit_batches,
            "preemptions": self.preemptions,
            "page_stalls": self.page_stalls,
            "restores": self.restores,
            "cancels": self.cancels,
            "submitted": self.submitted,
            "admits": self.admits,
        }
        for key in _POOL_GAUGES:            # publish the derived gauges
            self._obs_series[key].set(st[key])
        return st

    # -- admission ----------------------------------------------------------
    def _try_seat(self, need: int) -> int | None:
        """Reserve one slot plus ``need`` sub-pages in the slot's own bank
        — both CPM lookups on the metadata devices.  A slot whose bank is
        out of pages is set aside and the next bank's slots are probed;
        on failure everything probed is released and the caller leaves
        the session queued."""
        held: list[int] = []
        try:
            while True:
                slot = self.alloc.alloc()   # CPM free-slot lookup
                if slot is None:
                    return None
                lo, hi = self._page_range(self._bank_of(slot))
                if self.alloc.alloc_pages(slot, need, lo, hi) is not None:
                    return slot
                held.append(slot)           # bank out of pages; try the next
        finally:
            for s in held:
                self.alloc.free(s)

    def _admit(self) -> None:
        """Admit queued sessions that fit this step.

        Seating is two-resource admission control: a session needs a free
        slot AND its initial page grant (``ceil((prompt+1)/page_size)``
        fresh, the saved page count parked) in the slot's bank.  Sessions
        that do not fit stay queued in FIFO position.  The admission
        *plan* (``repro.serve.gateway.admission``) splits the seated
        window into parked-session restore groups (bucketed by saved page
        count, no prefill) and same-prompt-length buckets of fresh
        sessions; every bucket pays ONE stacked prefill launch + ONE
        scatter program regardless of its size.  With
        ``admit_batching=False`` every group has one member — the strict
        FIFO baseline."""
        from .gateway import admission
        take = min(self._free_hint, self.table.waiting_count())
        if not take:
            return
        seated: dict[int, int] = {}
        granted = 0
        for sess in self.table.peek_waiting(take):
            need = (sess.parked.n_pages if sess.phase == PARKED
                    else self._grant0(sess.prompt_len))
            slot = self._try_seat(need)
            if slot is None:
                continue                    # stays queued, FIFO order kept
            seated[sess.sid] = slot
            self._free_hint -= 1
            granted += need
        if not seated:
            return
        with obs_tracing.span("pool.admission", cat="pool",
                              vclock=self._vclock,
                              args={"seated": len(seated),
                                    "pages": granted}) as sp:
            plan = admission.plan(
                [s for s in self.table.peek_waiting(take)
                 if s.sid in seated],
                batching=self.admit_batching)
            sp.args["restore_groups"] = len(plan.restores)
            sp.args["buckets"] = len(plan.buckets)
            for group in plan.restores:
                self._restore_group(list(group), seated)
            for bucket in plan.buckets:
                self._admit_bucket(list(bucket), seated)

    def _note_admit(self, sess, slot: int) -> None:
        """Host mirrors for one freshly seated session."""
        sess.admit_step = self.decode_steps
        if sess.first_admit_step < 0:
            sess.first_admit_step = self.decode_steps
            sess.seated_s = time.perf_counter()
        self.live[slot] = True
        self._temp[slot] = sess.gen.temperature
        self._topk[slot] = sess.gen.top_k
        self._topp[slot] = sess.gen.top_p

    def _page_table_rows(self, slots: list[int], width: int) -> np.ndarray:
        """Page-table rows for freshly seated ``slots``: each session's
        page list left-aligned into a ``(k, width)`` table, sentinel
        (``total_pages``) beyond the grant."""
        pt = np.full((len(slots), width), self.total_pages, np.int32)
        for i, slot in enumerate(slots):
            ids = self.alloc.pages(slot)
            pt[i, :len(ids)] = ids
        return pt

    def _scatter_token_pages(self, pairs) -> None:
        """Write freshly admitted/restored token rows into their banks:
        ``pairs`` is ``[(slot, row (max_len-or-shorter device/np array),
        row_len)]``; each row is page-chunked onto the slot's page list
        with per-page length registers."""
        per_bank: dict[int, list] = {}
        for slot, row, row_len in pairs:
            per_bank.setdefault(self._bank_of(slot), []).append(
                (slot, row, row_len))
        pg = self.page_size
        for bank_id, members in per_bank.items():
            base = bank_id * self.pages_per_bank
            idx: list[int] = []
            lens: list[int] = []
            chunks = []
            for slot, row, row_len in members:
                ids = self.alloc.pages(slot)
                n_live = self.pages_for(row_len)
                use = ids[:n_live]
                row = jnp.asarray(row, jnp.int32).reshape(-1)
                padded = jnp.zeros((n_live * pg,), jnp.int32)
                padded = padded.at[:row.shape[0]].set(row[:n_live * pg])
                idx += [p - base for p in use]
                lens += [min(pg, max(0, row_len - r * pg))
                         for r in range(n_live)]
                chunks.append(padded.reshape(n_live, pg))
            self.banks[bank_id].scatter(
                jnp.asarray(idx, jnp.int32), jnp.concatenate(chunks, 0),
                jnp.asarray(lens, jnp.int32))

    def _admit_bucket(self, bucket, seated: dict[int, int]) -> None:
        """Check a same-prompt-length bucket of fresh sessions in with one
        batched prefill and one scatter program."""
        engine = self.engine
        k, s = len(bucket), bucket[0].prompt_len
        ctx = obs_tracing.span("pool.admit_bucket", cat="pool",
                               vclock=self._vclock,
                               args={"sessions": k, "prompt_len": s,
                                     "sids": [se.sid for se in bucket]})
        with ctx:
            self._admit_bucket_inner(bucket, seated, k, s)

    def _admit_bucket_inner(self, bucket, seated, k: int, s: int) -> None:
        engine = self.engine
        slots = [seated[sess.sid] for sess in bucket]
        prompts = jnp.stack([sess.prompt for sess in bucket])
        with obs_tracing.span("pool.prefill", cat="pool",
                              vclock=self._vclock,
                              args={"sessions": k, "prompt_len": s}):
            logits, caches1 = engine._prefill(
                engine.params, batch={"tokens": prompts},
                max_len=self.max_len)
        caches1 = kv_cache.broadcast_lens(caches1, k)
        admit = engine._program("pool_admit", self.gen, self._build_admit,
                                s, k, self.slots, self.page_size,
                                self.pages_per_bank)
        self._rng, sub = jax.random.split(self._rng)
        rng = jax.random.fold_in(sub, bucket[0].sid)
        temp = jnp.asarray([se.gen.temperature for se in bucket], jnp.float32)
        topk = jnp.asarray([se.gen.top_k for se in bucket], jnp.int32)
        topp = jnp.asarray([se.gen.top_p for se in bucket], jnp.float32)
        pt = jnp.asarray(self._page_table_rows(slots, self.C))
        idx = jnp.asarray(slots, jnp.int32)
        self.caches, self.pos, self.cur, rows = admit(
            self.caches, caches1, idx, pt, self.pos, self.cur, logits,
            prompts, temp, topk, topp, rng)
        self.tok_lens = self.tok_lens.at[idx].set(s + 1)
        self.prefill_launches += 1
        self.admit_batches += 1
        self.admits += k
        for sess, slot in zip(bucket, slots):
            self.table.activate(sess.sid, self._bank_of(slot), slot)
            self._note_admit(sess, slot)
            sess.emitted = 1                # the prefill token
            self.total_emitted += 1
        self._scatter_token_pages(
            [(slot, rows[i], s + 1) for i, slot in enumerate(slots)])

    def _build_admit(self, s: int, k: int, slots: int, page_size: int,
                     pages_per_bank: int):
        """Jitted batched check-in for ``k`` prompts of length ``s``:
        sample each row's prefill token with its own sampling params,
        scatter the bucket's KV through the page table ``pt (k, C)``
        (global-attn leaves page-chunked into the sub-page pools —
        granted pages are fully rewritten, so nothing from their previous
        tenants survives; per-slot leaves written at rows ``idx``), seed
        pos/cur, and build the token rows."""
        del slots, page_size, pages_per_bank    # cache-key discriminators
        engine, width, cfg = self.engine, self.max_len, self.engine.cfg

        def run(pool_caches, new_caches, idx, pt, pos, cur, logits, prompts,
                temp, topk, topp, rng):
            first = sampling.sample_rows(logits[:, -1], rng, temp, topk,
                                         topp)
            caches = kv_cache.seat_caches(pool_caches, new_caches, cfg,
                                          idx, pt)
            pos = pos.at[idx].set(s)
            cur = cur.at[idx].set(first)
            rows = (jnp.zeros((k, width), jnp.int32)
                    .at[:, :s].set(prompts)
                    .at[jnp.arange(k), s].set(first))
            return caches, pos, cur, rows

        return jax.jit(run) if engine._jit else run

    # -- preemption (mechanism) ---------------------------------------------
    def park(self, sid: int) -> None:
        """Preempt an ACTIVE session: save its LIVE sub-pages into a
        host-side :class:`PageState`, free its slot and whole page list,
        and re-queue it at the FIFO tail for a later token-identical
        restore.  The *policy* — who gets parked, and when — lives in
        ``repro.serve.gateway.preempt``."""
        sess = self.table.get(sid)
        if sess.phase != ACTIVE:
            raise ValueError(f"session {sid} is {sess.phase}, not active")
        if sess.finished:
            raise ValueError(f"session {sid} already hit its budget; "
                             "step() will retire it")
        slot = sess.slot
        row_len = sess.prompt_len + sess.emitted
        n_live = self.pages_for(row_len)
        with obs_tracing.span("pool.park", cat="pool", vclock=self._vclock,
                              args={"sid": sid, "pages": n_live}):
            row = self._read_row(sess, "park")
            pt1 = jnp.asarray(
                self._page_table_rows([slot], n_live)[:, :n_live])
            image = kv_cache.lift_slot(self.caches, self.engine.cfg, slot,
                                       pt1)
            sess.parked = PageState(
                caches=jax.device_get(image), pos=int(self.pos[slot]),
                cur=int(self.cur[slot]), row=np.asarray(row),
                row_len=row_len, n_pages=n_live)
            sess.parks += 1
            self.preemptions += 1
            self.table.park(sid)
            self._release(slot)

    def _release(self, slot: int) -> None:
        """Slot + page list back to the free files, mirrors pinned."""
        self.alloc.free(slot)
        self._free_hint += 1
        self.live[slot] = False
        self.pos = self.pos.at[slot].set(0)
        self.cur = self.cur.at[slot].set(0)
        self.tok_lens = self.tok_lens.at[slot].set(0)

    def _restore_group(self, group, seated: dict[int, int]) -> None:
        """Re-admit parked sessions (all with the same saved page count,
        the planner's grouping key): ONE scatter program re-seats the
        whole group's saved sub-pages/pos/cur images (no prefill — the
        saved pages already hold the history), then each token row
        scatters back onto its new page list."""
        k = len(group)
        states = [sess.parked for sess in group]
        ctx = obs_tracing.span("pool.restore", cat="pool",
                               vclock=self._vclock,
                               args={"sessions": k,
                                     "pages": states[0].n_pages})
        with ctx:
            self._restore_group_inner(group, seated, states)

    def _restore_group_inner(self, group, seated, states) -> None:
        k = len(group)
        slots = [seated[sess.sid] for sess in group]
        n_live = states[0].n_pages
        blocks = jax.tree.map(lambda *xs: jnp.stack(xs, axis=1),
                              *[st.caches["blocks"] for st in states])
        tail = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                            *[st.caches["tail"] for st in states])
        restore = self.engine._program("pool_restore", self.gen,
                                       self._build_restore, k, n_live,
                                       self.slots, self.page_size,
                                       self.pages_per_bank)
        pt = jnp.asarray(self._page_table_rows(slots, n_live))
        idx = jnp.asarray(slots, jnp.int32)
        self.caches, self.pos, self.cur = restore(
            self.caches, blocks, tail, idx, pt, self.pos, self.cur,
            jnp.asarray([st.pos for st in states], jnp.int32),
            jnp.asarray([st.cur for st in states], jnp.int32))
        self.tok_lens = self.tok_lens.at[idx].set(
            jnp.asarray([st.row_len for st in states], jnp.int32))
        for sess, slot in zip(group, slots):
            self.table.activate(sess.sid, self._bank_of(slot), slot)
            self._note_admit(sess, slot)
            sess.parked = None
            self.restores += 1
        self._scatter_token_pages(
            [(slot, st.row, st.row_len)
             for slot, st in zip(slots, states)])

    def _build_restore(self, k: int, n_live: int, slots: int,
                       page_size: int, pages_per_bank: int):
        """Jitted batched re-seat for ``k`` parked sessions with ``n_live``
        saved sub-pages each: write the saved images through the page
        table and restore pos/cur — the decode stream continues exactly
        where preemption cut it."""
        del k, n_live, slots, page_size, pages_per_bank   # cache keys
        engine, cfg = self.engine, self.engine.cfg

        def run(pool_caches, blocks, tail, idx, pt, pos, cur, spos, scur):
            caches = kv_cache.seat_caches(
                pool_caches, {"blocks": blocks, "tail": tail}, cfg, idx, pt)
            return caches, pos.at[idx].set(spos), cur.at[idx].set(scur)

        return jax.jit(run) if engine._jit else run

    def victim_session(self):
        """The allocator's LRU eviction candidate (§7.5 min-over-ticks on
        the metadata device) as a Session, or None when nothing is
        evictable."""
        slot = self.alloc.victim()
        return self.table.at_slot(slot) if slot is not None else None

    # -- cancellation / inspection ------------------------------------------
    def _read_row(self, sess, site: str) -> np.ndarray:
        """A session's token content reassembled from its live sub-pages
        (host copy: page ids up, one bank gather, the pages down).
        ``site`` names the caller: ``stream``, ``retire``, ``cancel`` or
        ``park``."""
        row_len = sess.prompt_len + sess.emitted
        n_live = self.pages_for(row_len)
        with obs_tracing.span("pool.read_row", cat="pool",
                              args={"sid": sess.sid, "pages": n_live,
                                    "site": site}):
            base = self._bank_of(sess.slot) * self.pages_per_bank
            local = jnp.asarray(
                [p - base for p in self.alloc.pages(sess.slot)[:n_live]],
                jnp.int32)
            pages = np.asarray(self.banks[sess.bank].gather(local))
        return pages.reshape(-1)[:row_len]

    def _row_committed(self, sess) -> int:
        """Summed page-length registers of a session's live sub-pages —
        the bank's own view of how many tokens it holds."""
        row_len = sess.prompt_len + sess.emitted
        base = self._bank_of(sess.slot) * self.pages_per_bank
        local = [p - base for p in
                 self.alloc.pages(sess.slot)[:self.pages_for(row_len)]]
        lens = np.asarray(self.banks[sess.bank].lens)
        return int(lens[np.asarray(local, np.int64)].sum())

    def cancel(self, sid: int) -> np.ndarray:
        """Abort a session in any phase; returns prompt + whatever it
        generated before the cancel.  The tokens stay collectible (DONE)
        until the next drain/collect."""
        sess = self.table.get(sid)
        if sess.phase == DONE:
            return np.asarray(sess.tokens)
        if sess.phase == ACTIVE:
            row = self._read_row(sess, "cancel")
            self.table.finish(sid, row)
            self._release(sess.slot)
        elif sess.phase == PARKED:
            st = sess.parked
            self.table.finish(sid, np.asarray(st.row[:st.row_len]))
        else:                               # WAITING: nothing ran yet
            self.table.finish(sid, np.asarray(sess.prompt))
        self.cancels += 1
        return np.asarray(sess.tokens)

    def peek_tokens(self, sid: int) -> np.ndarray:
        """Host snapshot of a session's tokens so far (prompt + emitted),
        in any phase — what the gateway's streaming iterator reads."""
        sess = self.table.get(sid)
        if sess.phase == ACTIVE:
            return self._read_row(sess, "stream")
        if sess.phase == PARKED:
            return np.asarray(sess.parked.row[:sess.parked.row_len])
        if sess.phase == DONE:
            return np.asarray(sess.tokens)
        return np.asarray(sess.prompt)

    # -- decode -------------------------------------------------------------
    def _ensure_pages(self) -> None:
        """Host-side top-up between chunks: every active session gets
        enough slack pages to cover the next chunk's KV and token writes
        (so a page-boundary crossing never stalls the compiled step).
        Each bank's shortfalls, youngest session first, go out as ONE
        grant, which hands every session the ids a one-at-a-time loop
        would.  A bank that cannot cover them all runs that loop instead:
        the *youngest* sessions park — their pages free instantly for the
        older survivors, so the oldest session always progresses and a
        lone session can never livelock (submit bounds every session's
        worst case to one bank's capacity)."""
        order = sorted(self.table.active(),
                       key=lambda s: (s.first_admit_step, s.sid))
        with obs_tracing.span("pool.ensure_pages", cat="pool",
                              vclock=self._vclock) as sp:
            short = []                      # (sess, pages), youngest first
            for sess in reversed(order):
                need = min(self.C, self.pages_for(
                    sess.prompt_len + sess.emitted + self.chunk))
                have = len(self.alloc.pages(sess.slot))
                if need > have:
                    short.append((sess, need - have))
            by_bank: dict[int, list] = {}
            for sess, k in short:
                by_bank.setdefault(self._bank_of(sess.slot), []).append(
                    (sess.slot, k))
            dry = set()                     # banks that cannot cover all
            for bank, reqs in sorted(by_bank.items()):
                if self.alloc.grant_pages(
                        reqs, *self._page_range(bank)) is None:
                    dry.add(bank)
            topped = granted = 0
            for sess, k in short:           # youngest parks first if dry
                bank = self._bank_of(sess.slot)
                if bank in dry and self.alloc.alloc_pages(
                        sess.slot, k, *self._page_range(bank)) is None:
                    self.page_stalls += 1
                    self.park(sess.sid)
                else:
                    topped += 1
                    granted += k
            sp.args["sessions"] = topped
            sp.args["pages"] = granted

    def _decode_chunk(self) -> None:
        """One compiled program: gather every session's logical row
        through the page table, scan ``chunk`` decode steps, scatter back
        the dirty sub-pages, and commit each bank's tokens via the
        scheduler's packed ``insert -> truncate`` stream — no host
        round-trip inside."""
        active = self.table.active()
        with obs_tracing.span("pool.decode_chunk", cat="pool",
                              vclock=self._vclock,
                              args={"chunk": self.chunk,
                                    "active": len(active)}):
            run = self._chunk_program()
            self._rng, sub = jax.random.split(self._rng)
            args = self._chunk_args(active, sub)
            # the span times the dispatch: no device sync here (the tracer
            # must never add one; tests/test_obs.py asserts it)
            (self.cur, self.caches, self.pos, datas, lenss,
             self.tok_lens) = run(*args)
            for b, d, ln in zip(self.banks, datas, lenss):
                b.data, b.lens = d, ln

            for sess in active:             # host-mirror accounting only
                emit = min(self.chunk, sess.budget - sess.emitted)
                sess.emitted += emit
                self.total_emitted += emit
                self._decode_emitted += emit
            self.decode_steps += self.chunk
            self.sched.bank_launches += self.n_banks  # packed commits
            self.sched.streams_packed += len(active)

    def _chunk_program(self):
        """The compiled decode chunk of this pool's geometry."""
        return self.engine._program(
            "pool_chunk", self.gen, self._build_chunk, self.slots,
            self.chunk, self.n_banks, self._bank_backend,
            self._bank_interpret, self.page_size, self.pages_per_bank)

    def _chunk_args(self, active, rng) -> tuple:
        """The decode chunk's arguments for the ``active`` sessions."""
        budget_left = np.zeros((self.slots,), np.int32)
        pt = np.full((self.slots, self.C), self.total_pages, np.int32)
        for sess in active:
            budget_left[sess.slot] = sess.budget - sess.emitted
            ids = self.alloc.pages(sess.slot)
            pt[sess.slot, :len(ids)] = ids
        return (self.engine.params, self.cur, self.caches, self.pos,
                jnp.asarray(self.live), jnp.asarray(budget_left),
                jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp), [b.data for b in self.banks],
                [b.lens for b in self.banks], jnp.asarray(pt),
                self.tok_lens, rng)

    def _build_chunk(self, slots: int, chunk: int, n_banks: int,
                     bank_backend: str, bank_interpret, page_size: int,
                     pages_per_bank: int):
        """Jitted pooled decode chunk, paged end to end: gather each
        session's FULL logical KV row and token row through the page
        table (``kv_cache.logical_view`` for the KV pools; the
        scalar-prefetch gather kernel for pallas token banks), run an
        inner scan of ``chunk`` ``lm.decode_step`` calls with per-row
        positions (dead rows stay pinned — pos frozen, token 0), commit
        the gathered token rows via the per-bank packed ``insert ->
        truncate`` stream (unchanged logical shapes, so it stays ONE
        fused launch per bank on pallas), then scatter back only the
        DIRTY sub-pages — ranks touched since the chunk began; clean
        pages keep the sentinel and drop.  Rows whose budget ends
        mid-chunk keep decoding into slack; ``emit`` clamps what the
        commit makes visible."""
        del bank_interpret                  # cache-key discriminator: the
        # interpret default below and the commit closures bake it in
        engine, cfg = self.engine, self.engine.cfg
        rpb, C, pg, ppb = (self.rows_per_bank, self.C, self.page_size,
                           pages_per_bank)
        total = self.total_pages
        commits = [self.sched.compiled_commit(b, chunk, rows=rpb)
                   for b in range(n_banks)]
        pallas = bank_backend == "pallas"
        if pallas:
            from repro.kernels import cpm_kernels as K
            interp = self.banks[0]._pallas_interpret()

            def rows_gather(data, idx):
                return K.gather_rows(data, idx, interpret=interp)

            def rows_scatter(data, idx, rows):
                return K.scatter_rows(data, idx, rows, interpret=interp)
        else:
            def rows_gather(data, idx):
                return jnp.take(data, idx, axis=0)

            def rows_scatter(data, idx, rows):
                return data.at[idx].set(rows)    # OOB (sentinel) drops

        def run(params, cur, caches, pos, live, budget_left, temp, topk,
                topp, datas, lenss, page_tbl, tok_lens, rng):
            pos0 = pos
            logical = kv_cache.logical_view(caches, cfg, page_tbl)

            def body(carry, _):
                tok, lcaches, pos, rng = carry
                rng, sub = jax.random.split(rng)
                logits, lcaches = lm.decode_step(params, cfg, tok[:, None],
                                                 lcaches, pos)
                nxt = sampling.sample_rows(logits[:, -1], sub, temp, topk,
                                           topp)
                nxt = jnp.where(live, nxt, 0)
                pos = jnp.where(live, pos + 1, pos)
                return (nxt, lcaches, pos, rng), nxt

            (cur, logical, pos, _), toks = jax.lax.scan(
                body, (cur, logical, pos, rng), None, length=chunk)
            toks = jnp.moveaxis(toks, 0, 1)              # (slots, chunk)
            emit = jnp.where(live, jnp.minimum(budget_left, chunk), 0)
            rank = jnp.arange(C)[None]                   # page ranks
            kv_dirty = rank >= (pos0 // pg)[:, None]     # (slots, C)
            caches = kv_cache.merge_paged(
                caches, logical, cfg,
                jnp.where(kv_dirty, page_tbl, total))
            new_d, new_l, new_tl = [], [], []
            for b in range(n_banks):
                rows = slice(b * rpb, (b + 1) * rpb)
                ptb = page_tbl[rows] - b * ppb           # (rpb, C) local ids
                flat = ptb.reshape(-1)
                lrows = rows_gather(
                    datas[b], jnp.clip(flat, 0, ppb - 1)).reshape(rpb,
                                                                  C * pg)
                lens_b = tok_lens[rows]
                d_rows, l_rows = commits[b](lrows, lens_b, toks[rows],
                                            emit[rows])
                tok_dirty = rank >= (lens_b // pg)[:, None]
                d = rows_scatter(
                    datas[b], jnp.where(tok_dirty, ptb, ppb).reshape(-1),
                    d_rows.reshape(rpb * C, pg))
                plens = jnp.clip(
                    l_rows[:, None] - jnp.arange(C)[None] * pg, 0, pg)
                ln = lenss[b].at[flat].set(plens.reshape(-1).astype(
                    lenss[b].dtype), mode="drop")
                new_d.append(d)
                new_l.append(ln)
                new_tl.append(l_rows)
            return (cur, caches, pos, new_d, new_l,
                    jnp.concatenate(new_tl))

        return jax.jit(run) if engine._jit else run

    # -- retirement ---------------------------------------------------------
    def _retire(self) -> None:
        """Retire the sessions that reached their budget: each one's bank
        length read, row read and release, in one ``pool.retire`` span
        (recorded only when something retires)."""
        done = [s for s in self.table.active() if s.finished]
        if not done:
            return
        with obs_tracing.span("pool.retire", cat="pool",
                              vclock=self._vclock,
                              args={"sessions": len(done)}):
            for sess in done:
                ln = self._row_committed(sess)
                assert ln == sess.prompt_len + sess.emitted, (
                    ln, sess.prompt_len, sess.emitted)
                self.table.finish(sess.sid, self._read_row(sess, "retire"))
                self._release(sess.slot)

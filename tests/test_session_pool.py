"""Continuous batching vs per-session static generation — differential.

The pool's contract: under greedy decoding, every session drained through
the paged pool is **token-identical** to running it alone through the
static scan engine — across ragged prompt lengths, ragged budgets,
oversubscription (more sessions than pages), multi-bank splits, and the
hybrid recurrent architecture.  Plus the engine's compiled-program cache
keying regression (shapes must key the cache, not just names).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs
from repro.models import lm
from repro.serve import Engine, GenConfig

jax.config.update("jax_platform_name", "cpu")

CFG = all_configs()["granite-8b"].smoke()
HYB = all_configs()["recurrentgemma-9b"].smoke()


@pytest.fixture(scope="module")
def granite():
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    return Engine(CFG, params, max_len=64)


@pytest.fixture(scope="module")
def hybrid():
    params = lm.init_params(HYB, jax.random.PRNGKey(0))
    return Engine(HYB, params, max_len=48)


def _prompt(seed, s, cfg):
    return jax.random.randint(jax.random.PRNGKey(seed), (s,), 0,
                              cfg.vocab_size)


def _solo(engine, prompt, budget):
    out, _ = engine.generate({"tokens": prompt[None]},
                             GenConfig(max_new_tokens=budget))
    return np.asarray(out[0])


# ---------------------------------------------------------------------------
# token identity
# ---------------------------------------------------------------------------

class TestPoolTokenIdentity:
    def test_oversubscribed_ragged_matches_solo(self, granite):
        """6 sessions over 4 pages (2 banks), ragged prompts AND budgets:
        every drained output equals its solo static generation."""
        lens = [8, 12, 10, 8, 16, 9]
        budgets = [5, 12, 3, 9, 1, 7]
        prompts = [_prompt(i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]

        pool = granite.session_pool(slots=4, n_banks=2)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)
        stats = pool.stats()
        assert stats["emitted"] == sum(budgets)
        assert 0.0 < stats["occupancy"] <= 1.0

    def test_single_bank_matches_solo(self, granite):
        prompts = [_prompt(10 + i, 8, CFG) for i in range(3)]
        pool = granite.session_pool(slots=2, n_banks=1)
        sids = [pool.submit(p, 6) for p in prompts]
        outs = pool.drain()
        for sid, p in zip(sids, prompts):
            np.testing.assert_array_equal(outs[sid], _solo(granite, p, 6))

    def test_hybrid_arch_matches_solo(self, hybrid):
        """Recurrent (rglru) state + local-window rings page in and out of
        the pool rows without perturbing other sessions."""
        lens, budgets = [10, 14, 10], [6, 3, 8]
        prompts = [_prompt(20 + i, s, HYB) for i, s in enumerate(lens)]
        want = [_solo(hybrid, p, b) for p, b in zip(prompts, budgets)]
        pool = hybrid.session_pool(slots=2)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)

    def test_late_arrivals_match_solo(self, granite):
        """Sessions submitted mid-flight join free pages without touching
        in-flight rows."""
        first = [_prompt(30 + i, 8, CFG) for i in range(2)]
        late = [_prompt(40 + i, 11, CFG) for i in range(2)]
        pool = granite.session_pool(slots=2)
        sids = [pool.submit(p, 8) for p in first]
        pool.step()
        pool.step()
        sids += [pool.submit(p, 4) for p in late]
        outs = pool.drain()
        for sid, (p, b) in zip(sids, [(p, 8) for p in first]
                               + [(p, 4) for p in late]):
            np.testing.assert_array_equal(outs[sid], _solo(granite, p, b))

    @pytest.mark.parametrize("chunk", [3, 8])
    def test_chunked_decode_matches_solo(self, granite, chunk):
        """Decoding ``chunk`` tokens per compiled step (sessions finishing
        mid-chunk overshoot into slack; the commit clamps to budget) emits
        the identical tokens at any chunk size."""
        lens = [8, 12, 10, 9]
        budgets = [5, 11, 2, 7]               # none a multiple of chunk
        prompts = [_prompt(90 + i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]
        pool = granite.session_pool(slots=2, chunk=chunk)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)

    def test_pallas_banks_match_reference_banks(self, granite):
        """Token pages on pallas banks (fused commit launches + DMA
        gather/scatter kernels) drain the identical tokens."""
        prompts = [_prompt(50 + i, 9, CFG) for i in range(3)]
        ref = granite.session_pool(slots=2)
        pal = granite.session_pool(slots=2, bank_backend="pallas",
                                   bank_interpret=True)
        for p in prompts:
            ref.submit(p, 5)
            pal.submit(p, 5)
        r, q = ref.drain(), pal.drain()
        for sid in r:
            np.testing.assert_array_equal(r[sid], q[sid])


# ---------------------------------------------------------------------------
# lifecycle / API edges
# ---------------------------------------------------------------------------

class TestPoolLifecycle:
    def test_zero_budget_rejected(self, granite):
        """A degenerate budget is a caller error, not a no-op session —
        rejected before it can occupy queue or page state."""
        pool = granite.session_pool(slots=2)
        p = _prompt(60, 7, CFG)
        with pytest.raises(ValueError, match="must be positive"):
            pool.submit(p, 0)
        with pytest.raises(ValueError, match="must be positive"):
            pool.submit(p, -3)
        assert len(pool.table) == 0

    def test_empty_prompt_rejected(self, granite):
        pool = granite.session_pool(slots=2)
        with pytest.raises(ValueError, match="empty prompt"):
            pool.submit(np.zeros((0,), np.int32), 4)
        assert len(pool.table) == 0

    def test_budget_one_is_the_prefill_token(self, granite):
        pool = granite.session_pool(slots=2)
        p = _prompt(61, 7, CFG)
        sid = pool.submit(p, 1)
        outs = pool.drain()
        np.testing.assert_array_equal(outs[sid], _solo(granite, p, 1))

    def test_overlong_request_rejected(self, granite):
        pool = granite.session_pool(slots=2)
        with pytest.raises(ValueError, match="max_len"):
            pool.submit(_prompt(62, 60, CFG), 10)

    def test_pages_reclaimed(self, granite):
        pool = granite.session_pool(slots=2)
        for i in range(4):
            pool.submit(_prompt(70 + i, 8, CFG), 2)
        pool.drain()
        assert pool.alloc.free_count() == 2       # all pages back
        assert pool.table.all_done()

    def test_engine_submit_step_drain_facade(self, granite):
        params = lm.init_params(CFG, jax.random.PRNGKey(0))
        eng = Engine(CFG, params, max_len=64)
        p = _prompt(80, 8, CFG)
        sid = eng.submit(p, 3, slots=2)
        stats = eng.step()
        assert stats["emitted"] >= 1
        outs = eng.drain()
        np.testing.assert_array_equal(outs[sid], _solo(eng, p, 3))

    def test_bad_shapes_rejected(self, granite):
        with pytest.raises(ValueError, match="multiple"):
            granite.session_pool(slots=3, n_banks=2)

    def test_drain_delivers_each_session_once(self, granite):
        """Delivered sessions are evicted — a later drain returns only
        sessions finished since the last one (bounded table memory under
        a continuous stream)."""
        pool = granite.session_pool(slots=2)
        a = pool.submit(_prompt(85, 8, CFG), 2)
        first = pool.drain()
        assert set(first) == {a}
        b = pool.submit(_prompt(86, 8, CFG), 2)
        second = pool.drain()
        assert set(second) == {b}
        assert len(pool.table) == 0


# ---------------------------------------------------------------------------
# compiled-program cache keying (regression)
# ---------------------------------------------------------------------------

class TestProgramCacheKeying:
    def test_same_name_different_shapes_do_not_collide(self, granite):
        """Two builders under one name with different static shape args
        must compile separately — colliding returned the first shape's
        program for the second shape (the pool drives varying row counts
        through one engine)."""
        calls = []

        def builder(s):
            calls.append(s)
            return lambda: s

        gen = GenConfig(max_new_tokens=4)
        a = granite._program("probe", gen, builder, 8)
        b = granite._program("probe", gen, builder, 12)
        assert (a(), b()) == (8, 12)
        assert calls == [8, 12]
        # and the cache still memoizes identical keys
        assert granite._program("probe", gen, builder, 8) is a
        assert calls == [8, 12]

    def test_genconfig_arg_keys_via_key(self, granite):
        def builder(g):
            return lambda: g.max_new_tokens

        g1, g2 = GenConfig(max_new_tokens=4), GenConfig(max_new_tokens=9)
        assert granite._program("probe2", g1, builder, g1)() == 4
        assert granite._program("probe2", g2, builder, g2)() == 9

    def test_unhashable_builder_arg_rejected(self, granite):
        with pytest.raises(TypeError, match="statically hashable"):
            granite._program("probe3", GenConfig(), lambda a: a,
                             jnp.zeros((3,)))


# ---------------------------------------------------------------------------
# paged layout: sub-page banks + page-table attention
# ---------------------------------------------------------------------------

class TestPagedLayout:
    """``page_size < max_len``: KV and token storage become fixed-size
    sub-pages addressed through per-session page lists.  The contract is
    unchanged — every drained output is byte-identical to its solo static
    generation — while capacity is bounded by tokens resident, not by
    ``slots * max_len``."""

    def test_paged_ragged_matches_solo(self, granite):
        """Ragged prompts and budgets across page boundaries: sessions
        start inside one sub-page and grow across several mid-decode
        (slack pre-grant + host top-up), on 2 banks."""
        lens = [8, 12, 10, 9, 16, 7]
        budgets = [5, 12, 3, 9, 1, 14]
        prompts = [_prompt(200 + i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]
        pool = granite.session_pool(slots=4, n_banks=2, chunk=3,
                                    page_size=8, pages_per_bank=8)
        assert pool.C == 8 and pool.total_pages == 16
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)
        assert pool.alloc.page_free_count() == 16     # no sub-page leaked
        assert pool.alloc.free_count() == 4

    def test_page_pressure_parks_and_stays_identical(self, granite):
        """An under-provisioned page file (fewer sub-pages than the live
        set wants) forces mid-flight parks; the freed pages let older
        sessions finish and the parked ones restore token-identically."""
        lens = [8, 12, 10, 9]
        budgets = [9, 12, 6, 8]
        prompts = [_prompt(210 + i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]
        pool = granite.session_pool(slots=3, n_banks=1, chunk=2,
                                    page_size=4, pages_per_bank=9)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)
        assert pool.stats()["page_stalls"] > 0        # pressure actually hit
        assert pool.alloc.page_free_count() == 9

    def test_explicit_park_restore_paged(self, granite):
        """A mid-decode preempt saves ONLY live sub-pages; the restore
        (into whatever slot/pages are free then) continues the stream."""
        pa, pb = _prompt(220, 9, CFG), _prompt(221, 12, CFG)
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2,
                                    page_size=8, pages_per_bank=10)
        sa, sb = pool.submit(pa, 12), pool.submit(pb, 8)
        for _ in range(3):
            pool.step()
        sess = pool.table.get(sa)
        pool.park(sa)
        st = sess.parked
        assert st.n_pages == -(-st.row_len // 8)      # live pages only
        outs = pool.drain()
        np.testing.assert_array_equal(outs[sa], _solo(granite, pa, 12))
        np.testing.assert_array_equal(outs[sb], _solo(granite, pb, 8))
        assert pool.stats()["restores"] == 1

    def test_paged_pallas_banks_match_reference(self, granite):
        """Sub-page movement through the scalar-prefetch DMA kernels
        (gather logical rows -> fused commit -> scatter dirty pages)
        drains identical tokens to the reference jnp realization."""
        prompts = [_prompt(230 + i, 9, CFG) for i in range(4)]
        ref = granite.session_pool(slots=2, chunk=3, page_size=8,
                                   pages_per_bank=8)
        pal = granite.session_pool(slots=2, chunk=3, page_size=8,
                                   pages_per_bank=8,
                                   bank_backend="pallas",
                                   bank_interpret=True)
        for p in prompts:
            ref.submit(p, 7)
            pal.submit(p, 7)
        r, q = ref.drain(), pal.drain()
        for sid in r:
            np.testing.assert_array_equal(r[sid], q[sid])

    def test_hybrid_arch_paged_matches_solo(self, hybrid):
        """Only global-attn leaves page; rings and recurrent state stay
        per-slot and ride through park/grow untouched."""
        lens, budgets = [10, 14, 10], [6, 3, 8]
        prompts = [_prompt(240 + i, s, HYB) for i, s in enumerate(lens)]
        want = [_solo(hybrid, p, b) for p, b in zip(prompts, budgets)]
        pool = hybrid.session_pool(slots=2, page_size=8, pages_per_bank=10)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)

    def test_degenerate_page_size_is_whole_row_layout(self, granite):
        """Defaults (``page_size=None``) give pg = max_len, C = 1: one
        sub-page per session, the exact pre-paging layout."""
        pool = granite.session_pool(slots=2)
        assert pool.page_size == pool.max_len and pool.C == 1
        assert pool.pages_per_bank == 2                # rows_per_bank * C
        assert pool.total_pages == pool.slots          # one page per slot
        sid = pool.submit(_prompt(250, 8, CFG), 3)
        pool.step()
        sess = pool.table.get(sid)
        assert pool.alloc.pages(sess.slot) == [sess.slot]  # 1:1 with slot

    def test_bad_page_geometry_rejected(self, granite):
        with pytest.raises(ValueError, match="divisor"):
            granite.session_pool(slots=2, page_size=7)     # 64 % 7 != 0
        with pytest.raises(ValueError, match="divisor"):
            granite.session_pool(slots=2, page_size=0)
        with pytest.raises(ValueError, match="pages_per_bank"):
            granite.session_pool(slots=2, page_size=8, pages_per_bank=0)

    def test_submit_rejects_requests_beyond_bank_capacity(self, granite):
        """Regression: a request whose worst-case page count exceeds one
        bank's page file must be rejected at submit — it could never be
        seated, and previously nothing checked (satellite: no silent
        overflow/truncation)."""
        pool = granite.session_pool(slots=2, n_banks=1, chunk=2,
                                    page_size=8, pages_per_bank=3)
        with pytest.raises(ValueError, match="bank capacity"):
            pool.submit(_prompt(260, 20, CFG), 10)     # needs 4 pages
        assert len(pool.table) == 0
        # the same request fits a deeper page file
        deep = granite.session_pool(slots=2, n_banks=1, chunk=2,
                                    page_size=8, pages_per_bank=5)
        sid = deep.submit(_prompt(260, 20, CFG), 10)
        outs = deep.drain()
        np.testing.assert_array_equal(
            outs[sid], _solo(granite, _prompt(260, 20, CFG), 10))

    def test_paged_chunk_is_three_pallas_launches_per_bank(self, granite):
        """The compiled paged decode chunk on a pallas bank lowers to
        exactly THREE kernel launches per bank — the sub-page gather, the
        ONE fused insert->truncate commit mega-kernel (the pre-paging
        invariant, alive on the paged path), and the dirty-page scatter —
        regardless of chunk size or session count."""
        from repro.cpm.program import count_pallas_calls
        pool = granite.session_pool(slots=2, n_banks=1, chunk=3,
                                    page_size=8, pages_per_bank=8,
                                    bank_backend="pallas",
                                    bank_interpret=True)
        for i in range(2):
            pool.submit(_prompt(270 + i, 9, CFG), 8)
        pool.step()                                   # admit + first chunk
        run = pool.engine._program(
            "pool_chunk", pool.gen, pool._build_chunk, pool.slots,
            pool.chunk, pool.n_banks, "pallas", True, pool.page_size,
            pool.pages_per_bank)
        budget = jnp.asarray([8, 8], jnp.int32)
        pt = np.full((pool.slots, pool.C), pool.total_pages, np.int32)
        for sess in pool.table.active():
            ids = pool.alloc.pages(sess.slot)
            pt[sess.slot, :len(ids)] = ids
        n = count_pallas_calls(
            run, pool.engine.params, pool.cur, pool.caches, pool.pos,
            jnp.asarray(pool.live), budget, jnp.asarray(pool._temp),
            jnp.asarray(pool._topk), jnp.asarray(pool._topp),
            [b.data for b in pool.banks], [b.lens for b in pool.banks],
            jnp.asarray(pt), pool.tok_lens, jax.random.PRNGKey(7))
        assert n == 3 * pool.n_banks


# ---------------------------------------------------------------------------
# the between-chunk page top-up: one grant per bank, ordered under pressure
# ---------------------------------------------------------------------------

def _one_at_a_time_topup(pool):
    """What the top-up must do, replayed on an ``OracleAllocator`` that
    holds the pool's page lists: each session's shortfall granted alone,
    youngest first, a session that cannot be covered parked (its pages
    freed) on the spot.  Returns ``({sid: page list}, [parked sids])``."""
    from repro.cpm.pool import OracleAllocator
    orc = OracleAllocator(pool.slots, n_pages=pool.total_pages)
    active = pool.table.active()
    for sess in active:
        orc.used[sess.slot] = 0
        orc.page_lists[sess.slot] = pool.alloc.pages(sess.slot)
        orc.page_owner.update({p: sess.slot
                               for p in orc.page_lists[sess.slot]})
    parked = []
    for sess in sorted(active, key=lambda s: (s.first_admit_step, s.sid),
                       reverse=True):
        need = min(pool.C, pool.pages_for(
            sess.prompt_len + sess.emitted + pool.chunk))
        have = len(orc.pages(sess.slot))
        lo, hi = pool._page_range(pool._bank_of(sess.slot))
        if need > have and orc.alloc_pages(sess.slot, need - have,
                                           lo, hi) is None:
            parked.append(sess.sid)
            orc.free(sess.slot)
    return ({s.sid: orc.pages(s.slot) for s in active
             if s.sid not in parked}, parked)


def _grants_inside(span):
    from repro.obs import TRACER
    return [g for g in TRACER.spans("alloc.grant")
            if span.ts <= g.ts <= span.ts + span.dur]


class TestPageTopUp:
    def test_steady_decoding_is_one_grant_per_bank(self, granite):
        """Pages of 2 tokens and chunks of 2: every live session crosses
        a page each chunk, and each bank's shortfalls go out as one
        batched grant per tick; outputs stay identical to solo runs."""
        from repro.obs import TRACER
        lens = [6, 9, 7, 8]
        budgets = [12, 10, 14, 11]
        prompts = [_prompt(300 + i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]
        pool = granite.session_pool(slots=4, n_banks=2, chunk=2,
                                    page_size=2)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        TRACER.clear()
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)
        tops = TRACER.spans("pool.ensure_pages")
        assert len(tops) >= 4
        full = 0
        for sp in tops:
            grants = _grants_inside(sp)
            assert len(grants) <= pool.n_banks
            assert {g.args["path"] for g in grants} <= {"batched"}
            assert sum(g.args["requests"] for g in grants) \
                == sp.args["sessions"]
            assert sum(g.args["pages"] for g in grants) == sp.args["pages"]
            full += len(grants) == pool.n_banks and sp.args["sessions"] == 4
        assert full >= 3                    # both banks, all four sessions
        TRACER.clear()

    def test_page_pressure_takes_the_ordered_path(self, granite,
                                                  monkeypatch):
        """Under page pressure a bank that cannot cover its whole
        shortfall runs the ordered loop: the youngest parks first, and
        every tick leaves the page lists and parks the one-at-a-time
        replay on the oracle gives; outputs stay identical to solo runs."""
        from repro.obs import TRACER
        lens = [8, 12, 10, 9, 11, 8]
        budgets = [9, 12, 6, 8, 10, 7]
        prompts = [_prompt(310 + i, s, CFG) for i, s in enumerate(lens)]
        want = [_solo(granite, p, b) for p, b in zip(prompts, budgets)]
        pool = granite.session_pool(slots=4, n_banks=2, chunk=2,
                                    page_size=4, pages_per_bank=8)
        real_topup, real_park = pool._ensure_pages, pool.park
        parked: list[int] = []
        seen = {"ordered": 0, "batched": 0}

        def park(sid):
            parked.append(sid)
            real_park(sid)

        def checked_topup():
            lists, parks = _one_at_a_time_topup(pool)
            del parked[:]
            TRACER.clear()
            real_topup()
            assert parked == parks
            assert {s.sid: pool.alloc.pages(s.slot)
                    for s in pool.table.active()} == lists
            for g in TRACER.spans("alloc.grant"):
                seen[g.args["path"]] += 1

        monkeypatch.setattr(pool, "park", park)
        monkeypatch.setattr(pool, "_ensure_pages", checked_topup)
        sids = [pool.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = pool.drain()
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(outs[sid], w)
        assert pool.stats()["page_stalls"] > 0        # pressure hit
        assert seen["ordered"] > 0 and seen["batched"] > 0
        assert pool.alloc.page_free_count() == pool.total_pages
        TRACER.clear()

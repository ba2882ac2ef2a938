"""`repro.cpm.pool` — banks, the self-managing allocator, the MASIM packer.

Covers the pool subsystem's contracts:

  * the page-table allocator (whose free-list/victim lookups are CPM
    compare/limit ops) never double-books a page, never leaks one, and
    agrees with a naive Python oracle over random alloc/free/touch
    sequences (hypothesis);
  * bank page movement (scalar-prefetch gather/scatter kernels on pallas)
    is identical to the reference jnp realization;
  * the multi-bank scheduler packs per-slot streams into ONE batched
    launch per bank (fused on pallas, jaxpr-asserted), leaves idle rows'
    live regions bit-untouched, and rejects malformed packings.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cpm.pool import (CPMBank, MultiBankScheduler, OracleAllocator,
                            SessionTable, SlotAllocator)
from repro.cpm.program import count_pallas_calls

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# allocator: CPM bookkeeping vs the Python oracle
# ---------------------------------------------------------------------------

class TestSlotAllocator:
    def test_alloc_until_full_then_none(self):
        a = SlotAllocator(3)
        assert [a.alloc() for _ in range(4)] == [0, 1, 2, None]
        assert a.free_count() == 0 and a.used_count() == 3

    def test_free_then_lowest_first(self):
        a = SlotAllocator(4)
        for _ in range(4):
            a.alloc()
        a.free(2)
        a.free(0)
        assert a.alloc() == 0          # lowest free page wins (priority enc)
        assert a.alloc() == 2

    def test_double_free_raises(self):
        a = SlotAllocator(2)
        a.alloc()
        a.free(0)
        with pytest.raises(ValueError, match="double free"):
            a.free(0)

    def test_victim_is_lru(self):
        a = SlotAllocator(3)
        for _ in range(3):
            a.alloc()
        a.touch(0)                     # slot 1 is now the oldest
        assert a.victim() == 1
        a.touch(1)
        assert a.victim() == 2

    def test_victim_empty_pool(self):
        assert SlotAllocator(2).victim() is None

    def test_used_slots_packed_via_compact(self):
        a = SlotAllocator(5)
        for _ in range(4):
            a.alloc()
        a.free(1)
        a.free(3)
        assert a.used_slots() == [0, 2]

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_victim_tie_break_is_lowest_slot(self, backend):
        """Equal allocation ticks (forced directly — the public API keeps
        ticks unique via the clock) must break deterministically to the
        lowest used slot on every backend: enumerate_matches drains
        §6-Rule-6 style, lowest address first."""
        kw = {"backend": backend, "interpret": True} \
            if backend == "pallas" else {}
        a = SlotAllocator(4, **kw)
        for _ in range(4):
            a.alloc()
        a.free(0)                           # slots 1..3 used
        a._tick = jnp.full((4,), 7, jnp.int32)   # three-way tie
        assert a.victim() == 1
        a.free(1)
        assert a.victim() == 2

    @given(st.lists(st.integers(0, 9), min_size=4, max_size=4),
           st.lists(st.booleans(), min_size=4, max_size=4))
    @settings(max_examples=10, deadline=None)
    def test_victim_ties_match_naive_min_across_backends(self, ticks, used):
        """Arbitrary (possibly tying) tick vectors: both backends must
        pick min-tick-then-min-slot, the same answer a naive host scan
        gives."""
        n = 4
        want = min((t, s) for s, (t, u) in enumerate(zip(ticks, used))
                   if u)[1] if any(used) else None
        for kw in ({}, {"backend": "pallas", "interpret": True}):
            a = SlotAllocator(n, **kw)
            # force the exact occupancy/tick pattern under test
            a._state = jnp.asarray([1 if u else 0 for u in used], jnp.int32)
            a._tick = jnp.asarray(ticks, jnp.int32)
            assert a.victim() == want

    @given(st.lists(st.integers(0, 2), min_size=1, max_size=60))
    @settings(max_examples=20, deadline=None)
    def test_matches_oracle_never_double_books_never_leaks(self, moves):
        """Random alloc/free/touch trace: the CPM allocator and the Python
        oracle make identical decisions, no page is handed out twice, and
        free + used always covers the pool exactly."""
        n = 4
        cpm, orc = SlotAllocator(n), OracleAllocator(n)
        held: set[int] = set()
        for i, mv in enumerate(moves):
            if mv == 0:                                   # alloc
                got, want = cpm.alloc(), orc.alloc()
                assert got == want
                if got is not None:
                    assert got not in held                # never double-booked
                    held.add(got)
            elif mv == 1 and held:                        # free (deterministic
                slot = sorted(held)[i % len(held)]        # pick from the trace)
                cpm.free(slot)
                orc.free(slot)
                held.discard(slot)
            elif mv == 2 and held:                        # touch
                slot = sorted(held)[i % len(held)]
                cpm.touch(slot)
                orc.touch(slot)
            assert cpm.free_count() == orc.free_count() == n - len(held)
            assert cpm.used_slots() == orc.used_slots() == sorted(held)
            assert cpm.victim() == orc.victim()


# ---------------------------------------------------------------------------
# sub-page file: page-list allocation as CPM ops
# ---------------------------------------------------------------------------

class TestPagedAllocator:
    def test_alloc_pages_lowest_first_in_range(self):
        a = SlotAllocator(2, n_pages=8)
        s = a.alloc()
        assert a.alloc_pages(s, 2, 4, 8) == [4, 5]     # bank-1 range only
        assert a.alloc_pages(s, 1) == [0]              # global: lowest free
        assert a.pages(s) == [4, 5, 0]                 # ordered by grant
        assert a.page_free_count() == 5
        assert a.page_free_count(4, 8) == 2

    def test_alloc_pages_all_or_nothing(self):
        a = SlotAllocator(1, n_pages=4)
        s = a.alloc()
        assert a.alloc_pages(s, 3) == [0, 1, 2]
        assert a.alloc_pages(s, 2) is None             # only 1 left: claim
        assert a.page_free_count() == 1                # NOTHING of it
        assert a.pages(s) == [0, 1, 2]
        assert a.alloc_pages(s, 1) == [3]

    def test_pages_need_a_used_owner(self):
        a = SlotAllocator(2, n_pages=4)
        with pytest.raises(ValueError, match="owner"):
            a.alloc_pages(0, 1)
        s = a.alloc()
        with pytest.raises(ValueError, match="positive"):
            a.alloc_pages(s, 0)
        with pytest.raises(IndexError):
            a.alloc_pages(s, 1, 2, 9)                  # range out of bounds

    def test_free_releases_whole_page_list(self):
        a = SlotAllocator(2, n_pages=6)
        s0, s1 = a.alloc(), a.alloc()
        a.alloc_pages(s0, 3)
        a.alloc_pages(s1, 2)
        a.free(s0)                                     # retire: slot + pages
        assert a.page_free_count() == 4
        assert a.pages(s1) == [3, 4]                   # neighbor untouched
        s2 = a.alloc()
        assert a.alloc_pages(s2, 3) == [0, 1, 2]       # reclaimed, lowest-first

    def test_no_page_file_is_inert(self):
        a = SlotAllocator(2)                           # n_pages=0 default
        s = a.alloc()
        assert a.page_free_count() == 0
        assert a.pages(s) == []
        a.free(s)                                      # nothing to leak

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 7)),
                    min_size=1, max_size=60))
    @settings(max_examples=20, deadline=None)
    def test_page_traces_match_oracle_no_double_booking_no_leaks(self, moves):
        """Random alloc / alloc_pages(extend) / free(park-or-retire) /
        touch traces: the CPM allocator and the oracle hand out identical
        page lists, no sub-page is ever owned twice, and freeing a slot
        (retire, cancel and park all route through ``free``) returns its
        whole list — free + owned always covers the page file exactly."""
        n, npg = 3, 8
        cpm = SlotAllocator(n, n_pages=npg)
        orc = OracleAllocator(n, n_pages=npg)
        held: set[int] = set()
        for i, (mv, arg) in enumerate(moves):
            if mv == 0:                                   # alloc slot
                got, want = cpm.alloc(), orc.alloc()
                assert got == want
                if got is not None:
                    held.add(got)
            elif mv == 1 and held:                        # extend page list
                slot = sorted(held)[i % len(held)]
                k = 1 + arg % 3
                lo = (arg % 2) * (npg // 2)               # one bank's range
                got = cpm.alloc_pages(slot, k, lo, lo + npg // 2)
                want = orc.alloc_pages(slot, k, lo, lo + npg // 2)
                assert got == want                        # incl. both-None
            elif mv == 2 and held:                        # free = park/retire
                slot = sorted(held)[i % len(held)]
                cpm.free(slot)
                orc.free(slot)
                held.discard(slot)
            elif mv == 3 and held:                        # touch
                slot = sorted(held)[i % len(held)]
                cpm.touch(slot)
                orc.touch(slot)
            owned = [p for s in held for p in orc.pages(s)]
            assert len(owned) == len(set(owned))          # never double-booked
            for s in sorted(held):
                assert cpm.pages(s) == orc.pages(s)       # identical lists
            # free + owned covers the file exactly: nothing leaked
            assert (cpm.page_free_count() == orc.page_free_count()
                    == npg - len(owned))
            booked = set(np.flatnonzero(cpm.page_state_vector()))
            assert booked == set(owned)
            assert cpm.victim() == orc.victim()


    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_batched_grant_equals_one_at_a_time(self, backend):
        """A batched grant of several ``(slot, k)`` requests hands out the
        same ids, and leaves the same page file, as the same requests
        granted one at a time — here on a fragmented bank range."""
        kw = {"backend": backend, "interpret": True} \
            if backend == "pallas" else {}
        lists = []
        for batched in (True, False):
            a = SlotAllocator(4, n_pages=16, **kw)
            s = [a.alloc() for _ in range(4)]
            a.alloc_pages(s[0], 3, 8, 16)
            a.alloc_pages(s[1], 2, 8, 16)
            a.free(s[0])                               # frees 8, 9, 10
            assert a.alloc() == s[0]
            reqs = [(s[0], 2), (s[2], 3), (s[3], 1)]
            if batched:
                got = a.grant_pages(reqs, 8, 16)
            else:
                got = [a.alloc_pages(slot, k, 8, 16) for slot, k in reqs]
            assert got == [[8, 9], [10, 13, 14], [15]]
            lists.append(([a.pages(x) for x in s], a.page_state_vector()))
        assert lists[0][0] == lists[1][0]
        np.testing.assert_array_equal(lists[0][1], lists[1][1])

    def test_batched_grant_that_does_not_fit_claims_nothing(self):
        a = SlotAllocator(3, n_pages=8)
        s = [a.alloc() for _ in range(3)]
        a.alloc_pages(s[0], 2, 0, 4)
        before = a.page_state_vector().copy()
        assert a.grant_pages([(s[1], 1), (s[2], 2)], 0, 4) is None
        np.testing.assert_array_equal(a.page_state_vector(), before)
        assert a.pages(s[1]) == a.pages(s[2]) == []
        assert a.page_free_count(0, 4) == 2
        assert a.grant_pages([(s[1], 1), (s[2], 1)], 0, 4) == [[2], [3]]
        assert a.page_free_count(0, 4) == 0

    @given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 7)),
                    min_size=1, max_size=60))
    @settings(max_examples=20, deadline=None)
    def test_batched_grant_traces_match_oracle(self, moves):
        """Random traces with a batched-grant move beside alloc, one-page
        list growth, free and touch: identical grants (incl. both-None)
        and page lists as the oracle, nothing owned twice or leaked."""
        n, npg = 3, 8
        cpm = SlotAllocator(n, n_pages=npg)
        orc = OracleAllocator(n, n_pages=npg)
        held: set[int] = set()
        for i, (mv, arg) in enumerate(moves):
            lo = (arg % 2) * (npg // 2)                   # one bank's range
            if mv == 0:                                   # alloc slot
                got = cpm.alloc()
                assert got == orc.alloc()
                if got is not None:
                    held.add(got)
            elif mv == 1 and held:                        # extend one list
                slot = sorted(held)[i % len(held)]
                k = 1 + arg % 3
                assert (cpm.alloc_pages(slot, k, lo, lo + npg // 2)
                        == orc.alloc_pages(slot, k, lo, lo + npg // 2))
            elif mv == 2 and held:                        # free
                slot = sorted(held)[i % len(held)]
                cpm.free(slot)
                orc.free(slot)
                held.discard(slot)
            elif mv == 3 and held:                        # touch
                slot = sorted(held)[i % len(held)]
                cpm.touch(slot)
                orc.touch(slot)
            elif mv == 4 and held:                        # batched top-up
                reqs = [(slot, 1 + (arg + j) % 2)
                        for j, slot in enumerate(sorted(held))]
                assert (cpm.grant_pages(reqs, lo, lo + npg // 2)
                        == orc.grant_pages(reqs, lo, lo + npg // 2))
            owned = [p for s in held for p in orc.pages(s)]
            assert len(owned) == len(set(owned))
            for s in sorted(held):
                assert cpm.pages(s) == orc.pages(s)
            assert (cpm.page_free_count() == orc.page_free_count()
                    == npg - len(owned))
            assert set(np.flatnonzero(cpm.page_state_vector())) == set(owned)

    def test_grant_is_one_program_for_every_k_and_range(self):
        """50 grants of varying ``k``, ``lo`` and ``hi`` (single and
        batched) on one allocator lower the grant program at most once:
        a compile per ``k`` would land inside a serving window."""
        from repro.cpm.pool import allocator
        a = SlotAllocator(2, n_pages=56)
        s0, s1 = a.alloc(), a.alloc()
        rng = np.random.default_rng(0)
        before = allocator._page_grant._cache_size()
        after_first = None
        for i in range(50):
            lo = int(rng.integers(0, 28))
            hi = int(rng.integers(lo + 1, 57))
            k = int(rng.integers(1, 6))
            if i % 3:
                a.alloc_pages((s0, s1)[i % 2], k, lo, hi)
            else:
                a.grant_pages([(s0, k), (s1, 1 + i % 4)], lo, hi)
            if i % 8 == 7:                             # pages back
                a.free(s1)
                assert a.alloc() == s1
            if after_first is None:
                after_first = allocator._page_grant._cache_size()
        assert after_first - before <= 1
        assert allocator._page_grant._cache_size() == after_first
        assert a.pages(s0) and a.page_free_count() < 56


# ---------------------------------------------------------------------------
# banks: paged row movement, reference vs pallas kernels
# ---------------------------------------------------------------------------

class TestCPMBank:
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_write_read_roundtrip(self, backend):
        b = CPMBank(4, 16, backend=backend, interpret=True)
        b.write_row(2, jnp.arange(5) + 1)
        row, ln = b.read_row(2)
        assert ln == 5
        np.testing.assert_array_equal(row[:5], [1, 2, 3, 4, 5])
        assert (row[5:] == 0).all()
        b.clear_row(2)
        assert b.read_row(2)[1] == 0

    def test_gather_scatter_pallas_matches_reference(self):
        key = jax.random.PRNGKey(0)
        data = jax.random.randint(key, (6, 32), 0, 100)
        lens = jnp.arange(6, dtype=jnp.int32) + 3
        ref = CPMBank(6, 32)
        pal = CPMBank(6, 32, backend="pallas", interpret=True)
        for b in (ref, pal):
            b.data, b.lens = data, lens
        idx = jnp.asarray([4, 0, 2], jnp.int32)
        np.testing.assert_array_equal(np.asarray(ref.gather(idx)),
                                      np.asarray(pal.gather(idx)))
        rows = jax.random.randint(jax.random.PRNGKey(1), (3, 32), 0, 100)
        new_lens = jnp.asarray([7, 8, 9], jnp.int32)
        ref.scatter(idx, rows, new_lens)
        pal.scatter(idx, rows, new_lens)
        np.testing.assert_array_equal(np.asarray(ref.data),
                                      np.asarray(pal.data))
        np.testing.assert_array_equal(np.asarray(ref.lens),
                                      np.asarray(pal.lens))
        # untouched pages kept their content
        np.testing.assert_array_equal(np.asarray(ref.data[1]),
                                      np.asarray(data[1]))

    def test_row_too_wide_raises(self):
        with pytest.raises(ValueError, match="width"):
            CPMBank(2, 4).write_row(0, jnp.arange(5))


# ---------------------------------------------------------------------------
# MASIM packer: one batched launch per bank
# ---------------------------------------------------------------------------

def _commit(used, tok):
    return [("insert", {"pos": used, "values": jnp.asarray([tok])}),
            ("truncate", {"new_len": used + 1})]


class TestMultiBankScheduler:
    def test_partial_bank_idle_rows_untouched(self):
        b = CPMBank(4, 12)
        for slot in range(4):
            b.write_row(slot, jnp.full((3,), 10 + slot), 3)
        before = np.asarray(b.data).copy()
        sched = MultiBankScheduler([b])
        for slot in (1, 3):
            sched.submit(0, slot, _commit(b.lens[slot], 90 + slot))
        assert sched.flush() == {"banks": 1, "streams": 2}
        for slot in (1, 3):
            row, ln = b.read_row(slot)
            assert ln == 4 and row[3] == 90 + slot
        for slot in (0, 2):                     # idle pages: live region
            row, ln = b.read_row(slot)          # bit-untouched, length kept
            assert ln == 3
            np.testing.assert_array_equal(row[:3], before[slot, :3])

    def test_full_bank_out_of_slot_order(self):
        """Regression: a full bank's operands must scatter by slot, not
        ride in queue order."""
        b = CPMBank(3, 8)
        sched = MultiBankScheduler([b])
        for slot in (2, 0, 1):                  # deliberately shuffled
            sched.submit(0, slot, _commit(b.lens[slot], 50 + slot))
        sched.flush()
        for slot in range(3):
            row, ln = b.read_row(slot)
            assert ln == 1 and row[0] == 50 + slot

    def test_multi_bank_routing_and_counters(self):
        banks = [CPMBank(2, 8), CPMBank(2, 8)]
        sched = MultiBankScheduler(banks)
        sched.submit(0, 0, _commit(banks[0].lens[0], 7))
        sched.submit(1, 1, _commit(banks[1].lens[1], 8))
        assert sched.flush() == {"banks": 2, "streams": 2}
        assert banks[0].read_row(0)[0][0] == 7
        assert banks[1].read_row(1)[0][0] == 8
        assert sched.bank_launches == 2 and sched.streams_packed == 2
        assert sched.flush() == {"banks": 0, "streams": 0}   # empty is fine

    def test_mixed_templates_raise(self):
        b = CPMBank(2, 8)
        sched = MultiBankScheduler([b])
        sched.submit(0, 0, _commit(b.lens[0], 1))
        sched.submit(0, 1, [("truncate", {"new_len": 0})])
        with pytest.raises(ValueError, match="template"):
            sched.flush()

    def test_partially_bound_operand_raises(self):
        """A dynamic operand supplied by only some streams must fail with
        the packing diagnostic, not a deep stacking TypeError."""
        b = CPMBank(2, 8)
        sched = MultiBankScheduler([b])
        sched.submit(0, 0, [("truncate", {"new_len": 3})])
        sched.submit(0, 1, [("truncate", {})])
        with pytest.raises(ValueError, match="dynamic operands"):
            sched.flush()

    def test_same_slot_twice_raises(self):
        b = CPMBank(2, 8)
        sched = MultiBankScheduler([b])
        sched.submit(0, 0, _commit(b.lens[0], 1))
        sched.submit(0, 0, _commit(b.lens[0], 2))
        with pytest.raises(ValueError, match="slot"):
            sched.flush()

    def test_array_static_operand_rejected(self):
        b = CPMBank(2, 8)
        sched = MultiBankScheduler([b])
        sched.submit(0, 0, [("insert", {"pos": b.lens[0],
                                        "values": jnp.asarray([1])}),
                            ("shift", {"start": 0, "end": 1,
                                       "shift": jnp.asarray(1)})])
        with pytest.raises(TypeError, match="static operands"):
            sched.flush()

    def test_pallas_bank_commit_is_one_fused_launch(self):
        """The packed insert->truncate template on a pallas bank lowers to
        exactly ONE fused_stream mega-kernel launch per flush — the MASIM
        claim in jaxpr terms."""
        def run(data, lens, toks):
            bank = CPMBank(4, 16, backend="pallas", interpret=True)
            bank.data, bank.lens = data, lens
            sched = MultiBankScheduler([bank])
            for slot in range(3):               # 3 of 4 slots commit
                sched.submit(0, slot, _commit(lens[slot], toks[slot]))
            sched.flush()
            return bank.data, bank.lens

        data = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 50)
        lens = jnp.asarray([3, 5, 0, 2], jnp.int32)
        toks = jnp.asarray([91, 92, 93, 94], jnp.int32)
        assert count_pallas_calls(run, data, lens, toks) == 1

        # and the pallas lowering matches the reference packer bit-for-bit
        pal_data, pal_lens = run(data, lens, toks)

        def run_ref(data, lens, toks):
            bank = CPMBank(4, 16)
            bank.data, bank.lens = data, lens
            sched = MultiBankScheduler([bank])
            for slot in range(3):
                sched.submit(0, slot, _commit(lens[slot], toks[slot]))
            sched.flush()
            return bank.data, bank.lens

        ref_data, ref_lens = run_ref(data, lens, toks)
        np.testing.assert_array_equal(np.asarray(pal_lens),
                                      np.asarray(ref_lens))
        for r in range(4):                      # identical live regions
            n = int(ref_lens[r])
            np.testing.assert_array_equal(np.asarray(pal_data)[r, :n],
                                          np.asarray(ref_data)[r, :n])


# ---------------------------------------------------------------------------
# session table: lifecycle plumbing
# ---------------------------------------------------------------------------

class TestSessionTable:
    def test_fifo_lifecycle(self):
        t = SessionTable()
        a = t.add(jnp.arange(3), 3, 5)
        b = t.add(jnp.arange(4), 4, 2)
        assert t.next_waiting() is a
        t.activate(a.sid, 0, 1)
        assert t.at_slot(1) is a and t.next_waiting() is b
        assert t.active_count() == 1 and t.waiting_count() == 1
        t.finish(a.sid, np.arange(8))
        assert t.at_slot(1) is None
        t.activate(b.sid, 0, 0)
        t.finish(b.sid, np.arange(6))
        assert t.all_done()
        assert set(t.outputs()) == {a.sid, b.sid}

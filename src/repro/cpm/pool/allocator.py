"""The self-managing page-table allocator: CPM bookkeeping for CPM banks.

The paper's §4.2 pitch is a memory that manages itself; the associative-
processor literature (arXiv:2203.00662) pushes the same idea one level up —
use the memory's *own* content-addressable ops for its bookkeeping.  This
allocator does exactly that: slot metadata (state code, last-use tick) lives
in ``CPMArray`` devices, and every query is a paper op —

  * free-slot lookup   = §6.1 broadcast ``compare(FREE)`` + Rule-6
                         priority-encoder drain (``enumerate_matches``);
  * LRU victim lookup  = §7.5 ``global_limit("min")`` over the masked tick
                         file, then one more compare to address the holder;
  * occupancy counters = §6 compare + Rule-6 ``count``;
  * reclamation        = §4.2 ``compact`` packing the used slot ids.

Writes (alloc/free/touch) are single-address broadcast writes — activate one
slot, write one word — mutated through ``.at[slot].set`` on the metadata
buffers.  The host only ever sees slot *numbers*; the search work happens in
the memory.  A claim (a slot, or a grant of pages) is one compiled program —
compare, drain and write together — so the host pays one dispatch and one
readback per question, not one per op.  A pure-Python oracle with identical
semantics lives in :class:`OracleAllocator` for the property-test suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import tracing as obs_tracing

from ..array import CPMArray
from ..reference import pe_array

FREE = 0
USED = 1

_NO_TICK = jnp.iinfo(jnp.int32).max

_STATIC = ("n_used", "backend", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _slot_claim(state, tick, clock, *, n_used, backend, interpret):
    """Claim the lowest free slot and stamp its tick: §6.1 broadcast
    ``compare(FREE)``, Rule-6 drain of one address, one broadcast write
    per file.  Returns the new files and the slot (``n`` when none is
    free, in which case nothing is written)."""
    flags = CPMArray(state, jnp.asarray(n_used, jnp.int32), backend,
                     interpret).compare(FREE)
    addrs, _ = pe_array.enumerate_matches(flags, max_out=1)
    slot = addrs[0]
    return (state.at[slot].set(USED, mode="drop"),
            tick.at[slot].set(clock, mode="drop"), slot)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _page_grant(pstate, lo, hi, k, *, n_used, backend, interpret):
    """Claim the ``k`` lowest free pages of ``[lo, hi)``, all-or-nothing.

    The range-masked §6.1 ``compare(FREE)`` and the Rule-6 drain
    (``enumerate_matches``, lowest ids first) over the whole file; the
    first ``k`` addresses are written ``USED`` only if all ``k`` exist.
    ``lo``, ``hi`` and ``k`` are traced, so one program serves every grant
    of a page file.  Returns the new file, the ordered ids (the first
    ``k`` granted, the rest the sentinel ``n``) and the ok flag."""
    n = pstate.shape[-1]
    flags = CPMArray(pstate, jnp.asarray(n_used, jnp.int32), backend,
                     interpret).compare(FREE)
    ids = jnp.arange(n, dtype=jnp.int32)
    addrs, valid = pe_array.enumerate_matches(
        flags & (ids >= lo) & (ids < hi), max_out=n)
    take = ids < k
    ok = pe_array.count_matches(valid & take) == k
    got = jnp.where(take & ok, addrs, n)
    return pstate.at[got].set(USED, mode="drop"), got, ok


class SlotAllocator:
    """Page-table allocator over ``n_slots`` sessions of one pool, plus an
    optional file of ``n_pages`` *sub-pages* with per-session page lists.

    ``backend``/``interpret`` route the metadata queries like any other
    ``CPMArray`` (``"auto"`` by default, the per-array rule of
    ``backends.auto_backend_name``: pallas for metadata resident on a TPU
    once the file is long enough, reference otherwise).
    Allocation is admission control, a host decision, so every query
    returns to the host; but each decision costs O(1) concurrent CPM
    steps, not a host-side scan over slots.  A claim is one compiled
    program and one readback: :meth:`alloc` (compare, drain, write slot
    and tick) and :meth:`grant_pages` (compare, drain, write pages), each
    compiled once per file size.  Ownership checks read the host mirror
    of the page lists, never the device.

    With ``n_pages > 0`` the allocator also owns the sub-page metadata
    file: :meth:`grant_pages` claims the lowest free pages of a bank's
    range for a list of sessions in ONE §6.1 broadcast compare + Rule-6
    drain, all-or-nothing, handing them out in request order (so a batch
    gets exactly the ids one-at-a-time grants would);
    :meth:`alloc_pages` is the one-session case.  The ordered page list
    rides on the owning slot and :meth:`free` releases slot and pages
    together, so a retire or cancel can never leak a sub-page.
    """

    def __init__(self, n_slots: int, backend: str = "auto",
                 interpret: bool | None = None, n_pages: int = 0):
        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got {n_slots}")
        if n_pages < 0:
            raise ValueError(f"n_pages must be >= 0, got {n_pages}")
        self.n_slots = n_slots
        self.n_pages = n_pages
        self._backend = backend
        self._interpret = interpret
        self._state = jnp.full((n_slots,), FREE, jnp.int32)
        self._tick = jnp.zeros((n_slots,), jnp.int32)
        self._clock = 0
        # sub-page metadata file + host mirror of the ordered page lists
        self._pstate = jnp.full((max(n_pages, 1),), FREE, jnp.int32)
        self._pages: dict[int, list[int]] = {}

    # -- CPMArray views of the metadata file --------------------------------
    def _dev(self, data) -> CPMArray:
        return CPMArray(data, jnp.asarray(self.n_slots, jnp.int32),
                        self._backend, self._interpret)

    def _pdev(self, data) -> CPMArray:
        return CPMArray(data, jnp.asarray(self.n_pages, jnp.int32),
                        self._backend, self._interpret)

    def _route(self, n_used: int) -> dict:
        return {"n_used": n_used, "backend": self._backend,
                "interpret": self._interpret}

    # -- queries (all CPM ops) ----------------------------------------------
    def free_count(self) -> int:
        return int(self._dev(self._state).count(FREE))

    def used_count(self) -> int:
        return int(self._dev(self._state).count(USED))

    def is_free(self, slot: int) -> bool:
        self._check(slot)
        return int(self._state[slot]) == FREE

    def alloc(self) -> int | None:
        """Claim the lowest free slot, or ``None`` when the pool is full.

        One §6.1 broadcast compare asserts every free slot's match line
        concurrently; the Rule-6 drain materializes the lowest address,
        which is marked used and stamped most recently used in the same
        program."""
        state, tick, slot = _slot_claim(self._state, self._tick,
                                        self._clock + 1,
                                        **self._route(self.n_slots))
        slot = int(slot)
        if slot >= self.n_slots:
            return None
        self._state, self._tick = state, tick
        self._clock += 1
        self._pages[slot] = []
        return slot

    # -- sub-page file (CPM ops on the page metadata device) ----------------
    def _prange(self, lo: int, hi: int | None) -> tuple[int, int]:
        hi = self.n_pages if hi is None else hi
        if not 0 <= lo <= hi <= self.n_pages:
            raise IndexError(f"page range [{lo}, {hi}) outside "
                             f"[0, {self.n_pages})")
        return lo, hi

    def page_free_count(self, lo: int = 0, hi: int | None = None) -> int:
        """Free sub-pages within ``[lo, hi)`` (a bank's range): one §6
        broadcast compare, Rule-6 count of the masked match lines."""
        if not self.n_pages:
            return 0
        lo, hi = self._prange(lo, hi)
        flags = self._pdev(self._pstate).compare(FREE)
        ids = jnp.arange(self.n_pages, dtype=jnp.int32)
        return int(pe_array.count_matches(flags & (ids >= lo) & (ids < hi)))

    def alloc_pages(self, slot: int, k: int, lo: int = 0,
                    hi: int | None = None) -> list[int] | None:
        """Grow ``slot``'s page list by the ``k`` lowest free sub-pages in
        ``[lo, hi)``, or ``None`` (nothing claimed) when fewer than ``k``
        are free — all-or-nothing, so a mid-decode top-up either fully
        covers the next chunk or parks the session."""
        got = self._grant([(slot, k)], lo, hi, "ordered")
        return None if got is None else got[0]

    def grant_pages(self, requests: list[tuple[int, int]], lo: int = 0,
                    hi: int | None = None) -> list[list[int]] | None:
        """Grow each ``(slot, k)`` of ``requests`` by ``k`` sub-pages of
        ``[lo, hi)`` in one grant: the lowest free ids, handed out in
        request order — the ids the same requests granted one at a time
        would get.  All-or-nothing over the whole batch: ``None``, and
        nothing claimed, when fewer than the summed ``k`` are free."""
        return self._grant(requests, lo, hi, "batched")

    def _grant(self, requests, lo, hi, path: str):
        """One §6.1 broadcast ``compare(FREE)`` (range-masked) asserts
        every candidate's match line, the Rule-6 priority-encoder drain
        materializes the lowest addresses, and the claim is written — one
        compiled program, one readback of the ids.  Spanned as
        ``alloc.grant``."""
        for slot, k in requests:
            self._check(slot)
            if slot not in self._pages:
                raise ValueError(f"slot {slot} is free; pages need an owner")
            if k <= 0:
                raise ValueError(f"page count must be positive, got {k}")
        lo, hi = self._prange(lo, hi)
        total = sum(k for _, k in requests)
        with obs_tracing.span("alloc.grant", cat="alloc",
                              args={"requests": len(requests),
                                    "pages": total, "path": path}):
            pstate, got, ok = _page_grant(self._pstate, lo, hi, total,
                                          **self._route(self.n_pages))
            got, ok = jax.device_get((got, ok))
        if not ok:
            return None
        self._pstate = pstate
        out, at = [], 0
        for slot, k in requests:
            ids = got[at:at + k].tolist()
            at += k
            self._pages[slot].extend(ids)
            out.append(ids)
        return out

    def pages(self, slot: int) -> list[int]:
        """``slot``'s ordered page list (logical rank -> sub-page id)."""
        self._check(slot)
        return list(self._pages.get(slot, []))

    def victim(self) -> int | None:
        """The least-recently-used *used* page (LRU eviction candidate).

        §7.5 ``global_limit("min")`` over the tick file (free slots masked
        to the identity), then one compare to address the minimum's
        holder.  ``None`` when nothing is allocated."""
        used = self._dev(self._state).compare(USED)
        if not bool(pe_array.any_match(used)):
            return None
        masked = jnp.where(used, self._tick, _NO_TICK)
        oldest = self._dev(masked).global_limit("min")
        hits = self._dev(masked).compare(oldest)
        addrs, _ = pe_array.enumerate_matches(hits & used, max_out=1)
        return int(addrs[0])

    def used_slots(self) -> list[int]:
        """Used page ids packed to the front — the §4.2 ``compact`` of the
        slot-id file under the used flags (the reclamation/packing query
        the serving pool gathers live rows with)."""
        used = self._dev(self._state).compare(USED)
        ids = self._dev(jnp.arange(self.n_slots, dtype=jnp.int32))
        packed = ids.compact(used, fill=-1)
        k = int(packed.used_len)
        return [int(v) for v in np.asarray(packed.data[:k])]

    # -- transitions (single-address broadcast writes) ----------------------
    def free(self, slot: int) -> None:
        """Release ``slot`` AND its whole page list — retire, cancel and
        park all come through here, so sub-pages cannot leak."""
        self._check(slot)
        if slot not in self._pages:
            raise ValueError(f"double free of slot {slot}")
        self._state = self._state.at[slot].set(FREE)
        held = self._pages.pop(slot)
        if held:
            self._pstate = self._pstate.at[jnp.asarray(held)].set(FREE)

    def touch(self, slot: int) -> None:
        """Stamp ``slot`` as most recently used (LRU bookkeeping)."""
        self._check(slot)
        self._clock += 1
        self._tick = self._tick.at[slot].set(self._clock)

    def _check(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.n_slots})")

    # -- test hooks ---------------------------------------------------------
    def state_vector(self) -> np.ndarray:
        return np.asarray(self._state)

    def page_state_vector(self) -> np.ndarray:
        return np.asarray(self._pstate[:self.n_pages])


class OracleAllocator:
    """Naive host-side allocator with identical semantics — the property
    tests' differential oracle (no CPM ops, just Python)."""

    def __init__(self, n_slots: int, n_pages: int = 0):
        self.n_slots = n_slots
        self.n_pages = n_pages
        self.used: dict[int, int] = {}          # slot -> last-use tick
        self.page_lists: dict[int, list[int]] = {}   # slot -> ordered pages
        self.page_owner: dict[int, int] = {}         # page -> slot
        self._clock = 0

    def alloc(self) -> int | None:
        for s in range(self.n_slots):
            if s not in self.used:
                self._clock += 1
                self.used[s] = self._clock
                self.page_lists[s] = []
                return s
        return None

    def free(self, slot: int) -> None:
        del self.used[slot]
        for p in self.page_lists.pop(slot, []):
            del self.page_owner[p]

    def touch(self, slot: int) -> None:
        self._clock += 1
        self.used[slot] = self._clock

    def victim(self) -> int | None:
        if not self.used:
            return None
        oldest = min(self.used.values())
        return min(s for s, t in self.used.items() if t == oldest)

    def free_count(self) -> int:
        return self.n_slots - len(self.used)

    def used_slots(self) -> list[int]:
        return sorted(self.used)

    # -- sub-page file ------------------------------------------------------
    def alloc_pages(self, slot: int, k: int, lo: int = 0,
                    hi: int | None = None) -> list[int] | None:
        hi = self.n_pages if hi is None else hi
        got = [p for p in range(lo, hi) if p not in self.page_owner][:k]
        if len(got) < k:
            return None
        for p in got:
            self.page_owner[p] = slot
        self.page_lists.setdefault(slot, []).extend(got)
        return got

    def grant_pages(self, requests: list[tuple[int, int]], lo: int = 0,
                    hi: int | None = None) -> list[list[int]] | None:
        hi = self.n_pages if hi is None else hi
        free = [p for p in range(lo, hi) if p not in self.page_owner]
        if len(free) < sum(k for _, k in requests):
            return None
        return [self.alloc_pages(slot, k, lo, hi) for slot, k in requests]

    def pages(self, slot: int) -> list[int]:
        return list(self.page_lists.get(slot, []))

    def page_free_count(self, lo: int = 0, hi: int | None = None) -> int:
        hi = self.n_pages if hi is None else hi
        return sum(1 for p in range(lo, hi) if p not in self.page_owner)

"""Unit + property tests for the CPM operator library (`repro.cpm.reference`).

Migrated off the deprecated ``repro.core`` path (PR 4); the legacy shim itself
is covered on purpose in ``tests/test_core_shim.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.cpm.reference import (comparable, computable, movable,
                                 pe_array, searchable)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Rule 4 — general decoder
# ---------------------------------------------------------------------------

class TestGeneralDecoder:
    def test_basic_range(self):
        m = pe_array.activation_mask(16, 3, 9, 1)
        np.testing.assert_array_equal(np.where(m)[0], np.arange(3, 10))

    def test_carry(self):
        m = pe_array.activation_mask(32, 4, 20, 4)
        np.testing.assert_array_equal(np.where(m)[0], [4, 8, 12, 16, 20])

    @given(st.integers(0, 63), st.integers(0, 63), st.integers(1, 16))
    @settings(max_examples=50, deadline=None)
    def test_fused_equals_three_stage(self, start, end, carry):
        """The paper's carry-pattern -> shift -> all-line decomposition must
        equal the fused O(1) predicate."""
        fused = np.asarray(pe_array.activation_mask(64, start, end, carry))
        staged = np.asarray(pe_array.general_decoder(64, start, end, carry))
        np.testing.assert_array_equal(fused, staged)

    def test_paper_eq_3_1_carry_pattern(self):
        # 3/8 carry-pattern generator for carry=3: D[0], D[3], D[6]
        m = np.asarray(pe_array.carry_pattern(8, 3))
        np.testing.assert_array_equal(np.where(m)[0], [0, 3, 6])


class TestRule6:
    def test_counter_and_priority(self):
        match = jnp.array([False, True, False, True, True])
        assert int(pe_array.count_matches(match)) == 3
        assert int(pe_array.first_match(match)) == 1
        idx, valid = pe_array.enumerate_matches(match, 4)
        np.testing.assert_array_equal(np.asarray(idx), [1, 3, 4, 5])
        np.testing.assert_array_equal(np.asarray(valid), [True, True, True, False])

    def test_no_match(self):
        match = jnp.zeros(7, dtype=bool)
        assert int(pe_array.first_match(match)) == 7
        assert not bool(pe_array.any_match(match))

    def test_enumerate_matches_batched_slices_address_axis(self):
        """PR-3 regression: ``[:max_out]`` used to slice the *batch* axis,
        silently ignoring max_out and breaking the output shape."""
        match = jnp.array([[True, False, True, False, True],
                           [False, False, False, True, False],
                           [False, False, False, False, False]])
        idx, valid = pe_array.enumerate_matches(match, 2)
        assert idx.shape == valid.shape == (3, 2)
        np.testing.assert_array_equal(np.asarray(idx),
                                      [[0, 2], [3, 5], [5, 5]])
        np.testing.assert_array_equal(np.asarray(valid),
                                      [[True, True], [True, False],
                                       [False, False]])


# ---------------------------------------------------------------------------
# Content movable memory
# ---------------------------------------------------------------------------

class TestMovable:
    def test_shift_right(self):
        x = jnp.arange(8)
        out = np.asarray(movable.shift_range(x, 2, 5, 1))
        np.testing.assert_array_equal(out, [0, 1, 2, 2, 3, 4, 5, 7])

    def test_shift_left_with_fill(self):
        x = jnp.arange(8)
        out = np.asarray(movable.shift_range(x, 2, 5, -1, fill=-1))
        np.testing.assert_array_equal(out, [0, 2, 3, 4, 5, -1, 6, 7])

    def test_insert(self):
        x = jnp.array([10, 20, 30, 40, 0, 0, 0, 0])
        out = np.asarray(movable.insert(x, 1, jnp.array([99, 98]), 4))
        np.testing.assert_array_equal(out[:6], [10, 99, 98, 20, 30, 40])

    def test_delete(self):
        x = jnp.array([10, 20, 30, 40, 50, 0, 0, 0])
        out = np.asarray(movable.delete(x, 1, 2, 5))
        np.testing.assert_array_equal(out[:5], [10, 40, 50, 0, 0])

    def test_insert_then_delete_roundtrip(self):
        x = jnp.array([1, 2, 3, 4, 0, 0, 0, 0])
        y = movable.insert(x, 2, jnp.array([7, 8]), 4)
        z = np.asarray(movable.delete(y, 2, 2, 6))
        np.testing.assert_array_equal(z[:4], [1, 2, 3, 4])

    @given(st.lists(st.booleans(), min_size=1, max_size=32))
    @settings(max_examples=40, deadline=None)
    def test_compact_matches_numpy(self, keep):
        keep = np.asarray(keep)
        x = np.arange(len(keep)) + 100
        out, new_len = movable.compact(jnp.asarray(x), jnp.asarray(keep))
        assert int(new_len) == keep.sum()
        np.testing.assert_array_equal(np.asarray(out)[: keep.sum()], x[keep])

    @given(st.integers(2, 6), st.integers(1, 12), st.integers(0, 2 ** 16 - 1))
    @settings(max_examples=40, deadline=None)
    def test_compact_batched_matches_numpy(self, b, n, bits):
        """PR-3 regression: the tail mask used to broadcast ``(B,)`` lengths
        against the batch axis — a crash for B != n and silently wrong rows
        when B == n (exercised here by the b == n cases)."""
        keep = np.array([(bits >> (i % 16)) & 1 for i in range(b * n)],
                        dtype=bool).reshape(b, n)
        x = (np.arange(b * n) + 100).reshape(b, n)
        out, new_len = movable.compact(jnp.asarray(x), jnp.asarray(keep),
                                       fill=-1)
        np.testing.assert_array_equal(np.asarray(new_len), keep.sum(-1))
        for r in range(b):
            kept = keep[r].sum()
            np.testing.assert_array_equal(np.asarray(out)[r, :kept],
                                          x[r][keep[r]])
            np.testing.assert_array_equal(np.asarray(out)[r, kept:],
                                          np.full(n - kept, -1))

    def test_move_object(self):
        x = jnp.arange(10)
        out = np.asarray(movable.move_object(x, 2, 3, 6))
        np.testing.assert_array_equal(out[6:9], [2, 3, 4])
        np.testing.assert_array_equal(out[:6], np.arange(6))


# ---------------------------------------------------------------------------
# Content searchable memory
# ---------------------------------------------------------------------------

class TestSearchable:
    def test_substring_ends(self):
        hay = jnp.array(list(b"abracadabra"), dtype=jnp.int32)
        needle = jnp.array(list(b"abra"), dtype=jnp.int32)
        ends = np.where(np.asarray(searchable.substring_match(hay, needle)))[0]
        np.testing.assert_array_equal(ends, [3, 10])

    def test_find_all_starts(self):
        hay = jnp.array(list(b"aaaa"), dtype=jnp.int32)
        needle = jnp.array(list(b"aa"), dtype=jnp.int32)
        starts, valid = searchable.find_all(hay, needle, 4)
        np.testing.assert_array_equal(np.asarray(starts)[np.asarray(valid)], [0, 1, 2])

    @given(st.text(alphabet="ab", min_size=1, max_size=40),
           st.text(alphabet="ab", min_size=1, max_size=5))
    @settings(max_examples=40, deadline=None)
    def test_matches_python_find(self, hay_s, nee_s):
        if len(nee_s) > len(hay_s):
            return
        hay = jnp.array([ord(c) for c in hay_s], dtype=jnp.int32)
        nee = jnp.array([ord(c) for c in nee_s], dtype=jnp.int32)
        ends = set(np.where(np.asarray(searchable.substring_match(hay, nee)))[0])
        expect = {i + len(nee_s) - 1 for i in range(len(hay_s) - len(nee_s) + 1)
                  if hay_s[i : i + len(nee_s)] == nee_s}
        assert ends == expect

    def test_dynamic_needle_len(self):
        hay = jnp.array(list(b"xabcabz"), dtype=jnp.int32)
        nee = jnp.array(list(b"abc"), dtype=jnp.int32)
        ends = np.where(np.asarray(searchable.substring_match(hay, nee, needle_len=2)))[0]
        np.testing.assert_array_equal(ends, [2, 5])  # "ab" at 1 and 4

    def test_verify_draft(self):
        draft = jnp.array([5, 6, 7, 8])
        target = jnp.array([5, 6, 9, 8])
        assert int(searchable.verify_draft(draft, target)) == 2

    def test_ngram_lookup(self):
        ctx = jnp.array([1, 2, 3, 9, 1, 2, 3], dtype=jnp.int32)
        starts, valid = searchable.ngram_lookup(ctx, jnp.array([1, 2, 3], dtype=jnp.int32))
        got = np.asarray(starts)[np.asarray(valid)]
        np.testing.assert_array_equal(got, [3])  # continuation after first occurrence


# ---------------------------------------------------------------------------
# Content comparable memory
# ---------------------------------------------------------------------------

class TestComparable:
    def test_compare_ops(self):
        x = jnp.array([1, 5, 3, 5])
        assert int(pe_array.count_matches(comparable.compare(x, 5, "eq"))) == 2
        assert int(pe_array.count_matches(comparable.compare(x, 4, "lt"))) == 2

    def test_lex_compare(self):
        words = jnp.array([[1, 9], [2, 0], [1, 2], [2, 1]])  # MSW first
        lt = np.asarray(comparable.lex_compare_lt(words, jnp.array([2, 1])))
        np.testing.assert_array_equal(lt, [True, True, True, False])

    @given(st.lists(st.integers(0, 255), min_size=1, max_size=32))
    @settings(max_examples=30, deadline=None)
    def test_histogram_matches_numpy(self, vals):
        x = jnp.array(vals)
        edges = jnp.array([0, 64, 128, 192, 256])
        h = np.asarray(comparable.histogram(x, edges))
        np.testing.assert_array_equal(h, np.histogram(vals, bins=np.asarray(edges))[0])

    def test_quantile_threshold_topk(self):
        x = jnp.linspace(0.0, 1.0, 100)
        t = comparable.quantile_threshold(x, 10, 0.0, 1.0)
        assert int((x > t).sum()) in (9, 10)

    @given(st.integers(1, 8), st.integers(0, 6))
    @settings(max_examples=30, deadline=None)
    def test_topk_mask(self, k, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (3, 12))
        m = comparable.topk_mask(x, k)
        assert np.all(np.asarray(m.sum(-1)) == k)
        # masked-in values must all be >= every masked-out value
        lo = np.where(np.asarray(m), np.asarray(x), np.inf).min(-1)
        hi = np.where(np.asarray(m), -np.inf, np.asarray(x)).max(-1)
        assert np.all(lo >= hi - 1e-6)


# ---------------------------------------------------------------------------
# Content computable memory
# ---------------------------------------------------------------------------

class TestComputable:
    @given(st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                    min_size=1, max_size=300))
    @settings(max_examples=30, deadline=None)
    def test_section_sum(self, vals):
        x = jnp.array(vals, dtype=jnp.float32)
        np.testing.assert_allclose(float(computable.section_sum(x)),
                                   np.sum(np.asarray(x, dtype=np.float64)),
                                   rtol=1e-4, atol=1e-3)

    def test_section_sum_steps_sqrtN(self):
        n = 4096
        assert computable.section_sum_steps(n) <= 2 * int(np.sqrt(n)) + 1

    def test_section_limit(self):
        x = jnp.array([3.0, -7.0, 11.0, 0.5])
        assert float(computable.section_limit(x, mode="max")) == 11.0
        assert float(computable.section_limit(x, mode="min")) == -7.0

    def test_section_sum_2d(self):
        x = jnp.arange(48, dtype=jnp.float32).reshape(6, 8)
        np.testing.assert_allclose(float(computable.section_sum_2d(x)), x.sum())

    def test_stencil_algebra_eq_7_10(self):
        """(1 2 1) == (1 1 0) # (0 1 1)."""
        got = computable.compose_taps([1, 1, 0], [0, 1, 1])
        np.testing.assert_array_equal(np.trim_zeros(got), [1, 2, 1])

    def test_stencil_algebra_eq_7_11(self):
        """(1 2 4 2 1) == (1 1 1)#(1 1 1) + (1)  — 5-pt Gaussian, 6 cycles."""
        got = computable.add_taps(computable.compose_taps([1, 1, 1], [1, 1, 1]), [1])
        np.testing.assert_array_equal(got, [1, 2, 4, 2, 1])

    def test_stencil_1d_gaussian(self):
        x = jnp.array([0.0, 0, 1, 0, 0])
        y = np.asarray(computable.stencil_1d(x, [1, 2, 1]))
        np.testing.assert_allclose(y[1:4], [1, 2, 1])

    def test_stencil_2d_eq_7_12(self):
        taps = computable.compose_taps([1, 1, 0], [0, 1, 1])
        t2d = np.outer([1, 2, 1], [1, 2, 1]) / 1
        x = jnp.zeros((7, 7)).at[3, 3].set(1.0)
        y = np.asarray(computable.stencil_2d(x, t2d))
        np.testing.assert_allclose(y[2:5, 2:5], t2d)

    @given(st.lists(st.floats(-50, 50, allow_nan=False, width=32,
                              allow_subnormal=False),
                    min_size=2, max_size=64))
    @settings(max_examples=25, deadline=None)
    def test_odd_even_full_sort(self, vals):
        x = jnp.array(vals, dtype=jnp.float32)
        out = np.asarray(computable.odd_even_sort(x))
        np.testing.assert_allclose(out, np.sort(vals), rtol=1e-6)

    @given(st.lists(st.floats(-50, 50, allow_nan=False, width=32,
                              allow_subnormal=False),
                    min_size=2, max_size=48))
    @settings(max_examples=20, deadline=None)
    def test_hybrid_sort(self, vals):
        x = jnp.array(vals, dtype=jnp.float32)
        out = np.asarray(computable.hybrid_sort(x))
        np.testing.assert_allclose(out, np.sort(vals), rtol=1e-6)

    def test_count_disorder(self):
        assert int(computable.count_disorder(jnp.array([1, 2, 3]))) == 0
        assert int(computable.count_disorder(jnp.array([3, 2, 1]))) == 2

    def test_detect_defects_peak_valley(self):
        x = jnp.array([1.0, 2, 9, 3, 4])     # 9 is a peak
        d = computable.detect_defects(x)
        assert bool(d["peak"][2])
        x = jnp.array([5.0, 6, 1, 7, 8])     # 1 is a valley
        d = computable.detect_defects(x)
        assert bool(d["valley"][2])

    def test_template_match_1d(self):
        data = jnp.array([9.0, 1, 2, 3, 9, 9, 1, 2, 3, 9])
        t = jnp.array([1.0, 2, 3])
        sad = np.asarray(computable.template_match_1d(data, t))
        assert sad[1] == 0 and sad[6] == 0
        assert np.all(sad[[0, 2, 3, 4, 5]] > 0)

    def test_template_match_2d(self):
        img = jnp.zeros((8, 8)).at[2:4, 3:5].set(jnp.array([[1.0, 2], [3, 4]]))
        t = jnp.array([[1.0, 2], [3, 4]])
        sad = np.asarray(computable.template_match_2d(img, t))
        assert sad[2, 3] == 0
        assert np.count_nonzero(sad == 0) == 1

    def test_line_detection_prefers_edge(self):
        img = jnp.zeros((16, 16)).at[8:, :].set(1.0)  # horizontal edge
        resp = np.asarray(computable.edge_along_x(img, 4))
        # interior rows only (roll wraps at the image border)
        assert np.abs(resp[7:9]).max() > np.abs(resp[3:6]).max()

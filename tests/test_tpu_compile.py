"""Compile the serving path's kernels for a TPU v5e without the chip.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets ``jit(...).lower(...).compile()`` run Mosaic on
the real kernels at real widths, which catches what interpret mode cannot
(block shapes off the (8, 128) tiling, unsupported vector ops).  Each test
also checks that the compiled HLO really holds the Pallas kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs these tests loads the TPU library, and a host
where it cannot be described skips them there.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cpm.array import CPMArray
from repro.cpm.program.executors import _blockr_candidates
from repro.kernels import cpm_kernels as K
from repro.kernels import flash_attention as fa


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, *shapes, out=None) -> str:
    return jax.jit(fn, out_shardings=out).lower(*shapes).compile().as_text()


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("seq", [512, 2048])
def test_flash_attention_granite_widths(one_chip, seq):
    q = _spec(one_chip, (1, 32, seq, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 8, seq, 128), jnp.bfloat16)
    hlo = _compiled_hlo(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in hlo


# the serving pool's token bank: 8 slots x max_len 1024 in 16-token pages
BANK = (512, 16)


def test_gather_rows_pool_bank(one_chip):
    hlo = _compiled_hlo(
        lambda x, i: K.gather_rows(x, i, interpret=False),
        _spec(one_chip, BANK), _spec(one_chip, (BANK[0],)))
    assert "tpu_custom_call" in hlo


def test_scatter_rows_pool_bank(one_chip):
    hlo = _compiled_hlo(
        lambda x, i, s: K.scatter_rows(x, i, s, interpret=False),
        _spec(one_chip, BANK), _spec(one_chip, (BANK[0],)),
        _spec(one_chip, BANK))
    assert "tpu_custom_call" in hlo


def test_fused_commit_block_r1(one_chip):
    """The pool's packed commit: insert 8 tokens per row, then truncate,
    one row per grid step over 8 rows of 1024."""
    r, n, k = 8, 1024, 8
    instrs = (("insert", (("k", k),), 2), ("truncate", (), 1))
    hlo = _compiled_hlo(
        lambda x, ul, p, v, t: K.fused_stream(x, ul, instrs, (p, v, t),
                                              block_r=1, interpret=False),
        _spec(one_chip, (r, n)), _spec(one_chip, (r,)),
        _spec(one_chip, (r, 1)), _spec(one_chip, (r, k)),
        _spec(one_chip, (r, 1)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("op", ["section_sum", "section_limit"])
def test_sectioned_reduction(one_chip, op):
    kern = {"section_sum": lambda x: K.section_sum(x, 1024, interpret=False),
            "section_limit": lambda x: K.section_limit(x, 1024, "max",
                                                       interpret=False)}[op]
    hlo = _compiled_hlo(kern, _spec(one_chip, (8, 4096)))
    assert "tpu_custom_call" in hlo


# every other row-batched kernel at a real width: (8, 2048) rows, sectioned
# reductions over (8, 4096) in 1024-lane sections
ROWS = (8, 2048)
ROW_KERNELS = {
    "shift_range": (lambda x: K.shift_range(x, 5, 1000, -3, 7,
                                            interpret=False), [ROWS]),
    "oddeven_sort": (lambda x: K.oddeven_sort(x, interpret=False), [ROWS]),
    "compare": (lambda x: K.compare(x, 7, "lt", interpret=False), [ROWS]),
    "histogram": (lambda x, e: K.histogram(x, e, 1024, interpret=False),
                  [(8, 4096), (17,)]),
    "super_sum": (lambda x: K.super_sum(x, 1024, interpret=False),
                  [(8, 4096)]),
    "super_limit": (lambda x: K.super_limit(x, 1024, "min",
                                            interpret=False), [(8, 4096)]),
    "template_match": (lambda x, t: K.template_match(x, t, interpret=False),
                       [ROWS, (16,)], jnp.float32),
    "substring_match": (lambda x, t: K.substring_match(x, t,
                                                       interpret=False),
                        [ROWS, (8,)]),
    "stencil": (lambda x: K.stencil(x, (0.1, 0.2, 0.4, 0.2, 0.1), wrap=False,
                                    interpret=False), [ROWS], jnp.float32),
    "compact": (lambda x, k: K.compact(x, k > 0, interpret=False),
                [ROWS, ROWS]),
}


@pytest.mark.parametrize("name", sorted(ROW_KERNELS))
def test_row_kernel(one_chip, name):
    fn, shapes, *dtype = ROW_KERNELS[name]
    hlo = _compiled_hlo(fn, *(_spec(one_chip, s, *dtype) for s in shapes))
    assert "tpu_custom_call" in hlo


def test_activate(one_chip):
    hlo = _compiled_hlo(lambda: K.activate(2048, 3, 900, 2, interpret=False),
                        out=one_chip)
    assert "tpu_custom_call" in hlo


# fused streams the executor lowers, one per producer dtype and transform:
# name -> (instructions, per-operand (rows or 1, width) and dtype)
STREAMS = {
    "commit": ((("insert", (("k", 8),), 2), ("truncate", (), 1)),
               [("r", 1), ("r", 8), ("r", 1)], jnp.int32),
    "compare": ((("compare", (("op", "eq"), ("has_mask", True),
                              ("ct", "int32")), 2),),
                [("r", 1), (1, 1)], jnp.int32),          # int8 producer
    "substring_match": ((("substring_match", (("m", 4), ("where", "end")),
                          1),), [(1, 4)], jnp.int32),   # int8 producer
    "shift_stencil": ((("shift", (("shift", 2), ("has_fill", True)), 2),
                       ("stencil", (("taps", (0.25, 0.5, 0.25)),
                                    ("wrap", True)), 0)),
                      [("r", 2), ("r", 1)], jnp.int32),  # f32 producer
}


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("r,block_r",
                         [(r, br) for r in (40, 64)
                          for br in _blockr_candidates(r)])
def test_fused_stream_tuner_candidates(one_chip, stream, r, block_r):
    """Every row blocking the autotuner may time compiles, at sizes where
    it tunes (R x N >= 2**15), including an R that is no multiple of 8."""
    instrs, operands, dtype = STREAMS[stream]
    n = 1024
    specs = [_spec(one_chip, (r, n)), _spec(one_chip, (r,))] + [
        _spec(one_chip, (r if rows == "r" else rows, k), dtype)
        for rows, k in operands]
    hlo = _compiled_hlo(
        lambda x, ul, *ops: K.fused_stream(x, ul, instrs, ops,
                                           block_r=block_r, interpret=False),
        *specs)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_pages", [1024, 4096])
def test_allocator_page_file_ops(one_chip, n_pages):
    """The slot allocator's queries on its page file as a TPU runs them
    once the file is long enough for ``backend="auto"`` to pick Pallas."""
    def queries(state):
        dev = CPMArray(state, jnp.asarray(n_pages, jnp.int32), "pallas",
                       False)
        return dev.compare(0), dev.global_limit("min")
    hlo = _compiled_hlo(queries, _spec(one_chip, (n_pages,)))
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("n_pages", [1024, 2048])
def test_allocator_page_grant_program(one_chip, n_pages):
    """The allocator's compiled page grant (compare, drain and claim in
    one program, ``lo``, ``hi`` and ``k`` traced) with the Pallas compare
    a TPU's ``backend="auto"`` picks for a page file this long."""
    from repro.cpm.pool.allocator import _page_grant

    def grant(state, lo, hi, k):
        return _page_grant(state, lo, hi, k, n_used=n_pages,
                           backend="pallas", interpret=False)
    scalar = _spec(one_chip, ())
    hlo = _compiled_hlo(grant, _spec(one_chip, (n_pages,)), scalar, scalar,
                        scalar)
    assert "tpu_custom_call" in hlo

"""The trace reduction on traces recorded on one v5e chip.

``data/kernels.xplane.pb``: three annotated ``bench.tick`` rounds of a
flash-attention call (2 x 8 heads x 256 x 128, 4 KV heads), a 2048^2
bf16 matmul-and-sum and a 100-row ``gather_rows``, traced with
``jax.profiler`` on a TPU v5 lite."""

import pathlib

import pytest

import counts
import devtrace
from peaks import peaks

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def kernels():
    return devtrace.summarize(str(DATA / "kernels.xplane.pb"))


def test_busy_inside_window(kernels):
    assert kernels.chips == 1
    assert 0 < kernels.busy_s < kernels.window_s
    # the three rounds ran 9 programs, no two at once
    assert [m.short for m in kernels.modules] == [
        "jit__lambda", "jit__lambda", "jit_gather_rows"] * 3
    assert kernels.busy_s == pytest.approx(
        sum(o.dur_ns for o in kernels.ops) * 1e-9, rel=0.05)


def test_kernels_and_shapes(kernels):
    fa = kernels.kernel("flash_attention")
    assert len(fa) == 3 and all(o.module == "jit__lambda" for o in fa)
    res, opd = devtrace.shapes(fa[0].name)
    assert res == [("bf16", (2, 8, 256, 128))]
    assert [s for _, s in opd] == [(2, 8, 256, 128), (2, 4, 256, 128),
                                   (2, 4, 256, 128)]
    g = kernels.kernel("gather_rows", module="jit_gather_rows")
    assert len(g) == 3
    assert devtrace.shapes(g[0].name)[0] == [("s32", (100, 1, 16))]


def test_roofline_share_is_a_share(kernels):
    pk = peaks("TPU v5 lite")
    for op in kernels.kernel("flash_attention"):
        flops, nbytes = counts.flash_attention_cost(2, 8, 4, 256, 128)
        least, bound = counts.roofline_s(flops, nbytes, pk)
        assert 0 < least < op.dur_ns * 1e-9
        assert bound == "memory"      # 256 keys: too few to be compute-bound


def test_idle_gaps_are_named(kernels):
    out = kernels.breakdown()
    assert 0 < len(out["device_ops"]) <= 10
    assert 0 < len(out["idle_gaps"]) <= 10
    labels = [g for g, _ in kernels.gaps]
    assert any(label.startswith("bench.tick") for label in labels)
    total_idle = sum(s for _, s in kernels.gaps)
    assert total_idle <= kernels.window_s - kernels.busy_s + 1e-9


def test_hlo_shapes_of_tuples():
    txt = ("%copy-start = (s32[100]{0:T(128)S(1)}, s32[100]{0:T(128)}, "
           "u32[]{:S(2)}) copy-start(s32[100]{0:T(128)} %idx.1)")
    res, opd = devtrace.shapes(txt)
    assert res == [("s32", (100,)), ("s32", (100,)), ("u32", ())]
    assert opd == [("s32", (100,))]
    assert devtrace.short_name(txt) == "copy-start"
    assert devtrace.short_name("jit_run(123)") == "jit_run"


def test_breakdown_counts_self_time():
    loop = devtrace.Op("%while.1 = s32[] while(s32[] %x)", 0, 100, "jit_run")
    body = devtrace.Op("%fusion.2 = f32[8] fusion(f32[8] %y)", 10, 60,
                       "jit_run")
    after = devtrace.Op("%copy.3 = f32[8] copy(f32[8] %z)", 120, 5, "jit_run")
    got = dict((o.short, ns) for o, ns in
               devtrace._self_times([body, after, loop]))
    assert got == {"while": 40, "fusion": 60, "copy": 5}

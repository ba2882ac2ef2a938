"""Operations and bytes of the program's kernels, computed from the shapes
of a call, and the least time they take at a chip's peaks.

The model's useful FLOPs are its family's (``families/``), which a metric
reader sees as ``run.counts``.  A new kernel brings its cost function in
its own metric reader.
"""

from __future__ import annotations


def flash_attention_cost(b: int, h: int, kvh: int, s: int, dh: int,
                         itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal prefill attention call over (b, h, s,
    dh) queries and (b, kvh, s, dh) keys and values: the causal half of
    the score and value products; q, k, v read once and o written once."""
    flops = 4.0 * b * h * dh * s * (s + 1) / 2
    nbytes = itemsize * b * dh * s * (2 * h + 2 * kvh)
    return flops, float(nbytes)


def gather_rows_bytes(k: int, n: int, itemsize: int = 4) -> float:
    """(K,) page indices into an (R, N) bank: K rows read, K rows
    written, and the index vector."""
    return float(2 * k * n * itemsize + 4 * k)


def scatter_rows_bytes(r: int, k: int, n: int, itemsize: int = 4) -> float:
    """K rows into an (R, N) bank, out of place: each of the R output
    rows is written once from one source row (the K new rows or the old
    row), and the index vector is read."""
    return float(2 * r * n * itemsize + 4 * k)


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time at the chip's peaks and the bound that sets it."""
    t_c, t_m = flops / peak["flops"], nbytes / peak["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

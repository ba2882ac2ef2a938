"""Operations and bytes the served work needs, computed from shapes.

Useful FLOPs count real work only: live rows, the routed top-k experts
(not the capacity padding the program computes), causal attention at
each row's real context, and logits only where the program computes
them (the last prompt position in prefill, every decode step).  A
multiply-add is two FLOPs.
"""

from __future__ import annotations

from weights import dims


def layer_matmul_flops(cfg: dict) -> float:
    """Weight-matrix FLOPs of one token through one layer."""
    m = dims(cfg)
    d, h, kvh, dh, f = m["d"], m["h"], m["kvh"], m["dh"], m["f"]
    attn = 2 * d * (h + 2 * kvh) * dh + 2 * h * dh * d
    if m["E"]:
        ffn = 2 * d * m["E"] + m["k"] * 2 * 3 * d * f
    else:
        ffn = 2 * 3 * d * f
    return float(attn + ffn)


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one query against ``keys`` keys, over
    all layers and heads."""
    m = dims(cfg)
    return 4.0 * m["L"] * m["h"] * m["dh"] * keys


def logits_flops(cfg: dict) -> float:
    m = dims(cfg)
    return 2.0 * m["d"] * m["V"]


def prefill_flops(cfg: dict, s: int) -> float:
    """One prompt of ``s`` tokens: every position through every layer,
    causal attention (position i sees i + 1 keys), logits at the last."""
    m = dims(cfg)
    return (s * m["L"] * layer_matmul_flops(cfg)
            + attention_flops(cfg, s * (s + 1) / 2) + logits_flops(cfg))


def decode_flops(cfg: dict, keys: int) -> float:
    """One decode token that attends to ``keys`` keys (its own included)."""
    m = dims(cfg)
    return (m["L"] * layer_matmul_flops(cfg) + attention_flops(cfg, keys)
            + logits_flops(cfg))


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

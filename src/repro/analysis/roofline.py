"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step-per-chip:

    compute    = HLO_FLOPs / (chips × peak_flops)      [bf16 peak]
    memory     = HLO_bytes / (chips × hbm_bw)          [HBM]
    collective = collective_bytes / ici_bw             [per-chip ICI bytes]

with the peaks of the device at hand (:func:`hw`, keyed by ``device_kind``).

``cost_analysis()`` visits while-loop bodies once, so HLO_FLOPs/bytes come
from the unrolled 1-unit / 2-unit probe extrapolation (dryrun.py), and
collective bytes come from parsing the optimized per-device HLO with
while-body trip-count multipliers (``known_trip_count``).

Per-op per-chip traffic model (ring schedules on the torus, g = group size):
    all-gather       out_bytes × (g-1)/g
    reduce-scatter   in_bytes  × (g-1)/g
    all-reduce       in_bytes  × 2(g-1)/g
    all-to-all       in_bytes  × (g-1)/g
    collective-permute  in_bytes
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e ("TPU v5 lite" to the compiler): Google Cloud documentation,
#: "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip
#: interconnect (ICI) per chip.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,      # bf16 FLOP/s per chip
        "hbm_bw": 819e9,           # HBM bytes/s per chip
        "ici_bw": 1600e9 / 8,      # ICI bytes/s per chip
    },
}

#: Priors for a host that is no TPU (CPU tests, dry runs): the v5e figures,
#: so a run off the chip prices work as the chip would.
CPU_PRIORS = PEAKS["TPU v5 lite"]


def hw() -> dict:
    """Peaks of the first local device.  A TPU whose ``device_kind`` has no
    entry in :data:`PEAKS` raises — an unknown chip is an error, not a
    default; other platforms get :data:`CPU_PRIORS`."""
    import jax
    device = jax.local_devices()[0]
    if device.platform != "tpu":
        return CPU_PRIORS
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for TPU device kind "
                       f"{device.device_kind!r}; add them to roofline.PEAKS "
                       f"with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\).*?body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"?known_trip_count"?[:=]\{"?n"?[:=]"?(\d+)"?\}')
_CALL_RE = re.compile(r"(?:to_apply|calls|condition|body)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


@dataclasses.dataclass
class CollectiveStats:
    per_chip_bytes: float = 0.0
    by_kind: dict = dataclasses.field(default_factory=lambda: defaultdict(float))
    op_counts: dict = dataclasses.field(default_factory=lambda: defaultdict(int))


def parse_hlo(hlo_text: str, total_devices: int) -> CollectiveStats:
    """Per-chip collective bytes for one execution of the compiled module."""
    # 1) split into computations
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            if line.lstrip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            comps[cur].append(line)

    # 2) call-graph multipliers (while bodies x trip count)
    mult: dict[str, float] = defaultdict(float)

    def visit(name: str, m: float):
        if name not in comps:
            return
        mult[name] += m
        for line in comps[name]:
            trip = 1.0
            tm = _TRIP_RE.search(line)
            wm = _WHILE_RE.search(line)
            if wm:
                if tm:
                    trip = float(tm.group(1))
                visit(wm.group(1), m * trip)
                cm = re.search(r"condition=%?([\w.\-]+)", line)
                if cm:
                    visit(cm.group(1), m * trip)
                continue
            for callee in _CALL_RE.findall(line):
                visit(callee, m)
            bm = _BRANCH_RE.search(line)
            if bm:
                for callee in bm.group(1).split(","):
                    visit(callee.strip().lstrip("%"), m)

    if entry is None:
        entry = next(iter(comps))
    visit(entry, 1.0)

    # 3) collective bytes
    stats = CollectiveStats()
    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        for line in lines:
            om = _OP_RE.match(line)
            if not om:
                continue
            result_text, kind = om.group(1), om.group(2)
            operand_text = line[om.end():]
            out_b = _shape_bytes(result_text)
            in_b = _shape_bytes(operand_text.split(")", 1)[0] + ")")
            if in_b == 0:
                in_b = out_b
            g = _group_size(line, total_devices)
            frac = (g - 1) / g if g > 1 else 0.0
            if kind == "all-gather":
                chip = out_b * frac
            elif kind == "reduce-scatter":
                chip = in_b * frac
            elif kind == "all-reduce":
                chip = 2 * in_b * frac
            elif kind == "all-to-all":
                chip = in_b * frac
            else:                                   # collective-permute
                chip = in_b
            stats.per_chip_bytes += m * chip
            stats.by_kind[kind] += m * chip
            stats.op_counts[kind] += int(m)
    return stats


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float) -> dict:
    peaks = hw()
    t_c = flops_per_chip / peaks["peak_flops"]
    t_m = bytes_per_chip / peaks["hbm_bw"]
    t_x = coll_bytes_per_chip / peaks["ici_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bound": dom, "step_s_lower_bound": max(t_c, t_m, t_x)}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (inference fwd), N = active params.

    D counted as processed tokens per step (decode: one token per sequence).
    Enc-dec: encoder params see src frames (seq/8 — the stub frontend's
    frame rate), decoder params see target tokens; decode touches only the
    decoder."""
    k = 6.0 if shape.kind == "train" else 2.0
    if shape.kind == "decode":
        toks = float(shape.global_batch)
    else:
        toks = float(shape.global_batch * shape.seq_len)
    n = cfg.active_param_count()
    if not cfg.enc_dec:
        return k * n * toks
    d, dh, h, kvh = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    enc_layer = d * dh * (h + 2 * kvh) + h * dh * d + 2 * d * cfg.d_ff + 2 * d
    n_enc = cfg.n_enc_layers * enc_layer
    n_dec = n - n_enc
    src_toks = float(shape.global_batch * max(shape.seq_len // 8, 16))
    if shape.kind == "decode":
        return k * n_dec * toks
    return k * (n_enc * src_toks + n_dec * toks)

"""Composable model layers (pure-functional: init_* builds param dicts,
apply_* consumes them).

Mixers: GQA attention (global / local-window / cross), RG-LRU (Griffin),
mLSTM (chunked-parallel matrix memory), sLSTM (stabilized scalar memory).
FFNs: SwiGLU / GELU / ReLU dense, and MoE with CPM comparable-memory top-k
routing (the paper's technique as a first-class feature).

Every mixer exposes three modes:
  fwd(x)                  — full-sequence training/prefill forward
  fwd(x) -> (y, cache)    — prefill returning a decode cache
  step(x_t, cache) -> (y_t, cache)  — single-token decode
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.kernels import ops
from repro.cpm.reference import comparable

Params = dict
COMPUTE_DTYPE = jnp.bfloat16


def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32) * scale)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "ln":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE + 3-axis M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, dh: int, theta: float) -> tuple:
    """positions (..., S) -> cos/sin (..., S, dh//2)."""
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections=None) -> jax.Array:
    """x: (B, S, H, dh); positions: (B, S) or (3, B, S) for M-RoPE."""
    dh = x.shape[-1]
    if mrope_sections is None:
        cos, sin = _rope_angles(positions, dh, theta)    # (B, S, dh/2)
    else:
        cos3, sin3 = _rope_angles(positions, dh, theta)  # (3, B, S, dh/2)
        parts_c, parts_s = [], []
        off = 0
        for i, sec in enumerate(mrope_sections):
            parts_c.append(cos3[i, ..., off:off + sec])
            parts_s.append(sin3[i, ..., off:off + sec])
            off += sec
        cos = jnp.concatenate(parts_c, -1)
        sin = jnp.concatenate(parts_s, -1)
    # angles in f32, rotation applied in the stream dtype: keeps the
    # x-sized rotated tensor (a sharding-boundary crosser) narrow
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA; global causal / local window / bidirectional / cross)
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, cross: bool = False) -> Params:
    d, dh, h, kvh = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h * dh)),
        "wk": _dense_init(ks[1], (d, kvh * dh)),
        "wv": _dense_init(ks[2], (d, kvh * dh)),
        "wo": _dense_init(ks[3], (h * dh, d), scale=1.0 / math.sqrt(h * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), jnp.float32)
        p["bk"] = jnp.zeros((kvh * dh,), jnp.float32)
        p["bv"] = jnp.zeros((kvh * dh,), jnp.float32)
    return p


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
                 kv_input: jax.Array | None = None):
    b, s, _ = x.shape
    dh, h, kvh = cfg.dh, cfg.n_heads, cfg.n_kv_heads
    kv_x = x if kv_input is None else kv_input
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = kv_x @ p["wk"].astype(dt)
    v = kv_x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, kv_x.shape[1], kvh, dh)
    v = v.reshape(b, kv_x.shape[1], kvh, dh)
    return q, k, v


def attention_fwd(p: Params, x: jax.Array, cfg: ModelConfig, positions,
                  *, causal=True, window=None, kv_input=None,
                  kv_positions=None, rope=True, with_cache=False):
    """Full-sequence attention.  Returns y or (y, cache)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, kv_input)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        kpos = positions if kv_positions is None else kv_positions
        k = apply_rope(k, kpos, cfg.rope_theta, cfg.mrope_sections)
    q = shard(q.transpose(0, 2, 1, 3), "bhsd")          # (B, H, S, dh)
    k = shard(k.transpose(0, 2, 1, 3), "bhsd")
    v = shard(v.transpose(0, 2, 1, 3), "bhsd")
    o = ops.attention(q, k, v, causal=causal, window=window)
    o = shard(o, "bhsd").transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.dh)
    y = shard(o @ p["wo"].astype(x.dtype), "btd")
    if not with_cache:
        return y
    cache = {"k": k, "v": v, "len": jnp.asarray(s, jnp.int32)}
    return y, cache


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=COMPUTE_DTYPE, window: int | None = None) -> Params:
    """Decode cache.  Local-window layers keep a ring buffer of `window`
    slots — sliding-window eviction is the paper's content-movable memory:
    the oldest entry is overwritten in place, O(1), where the cache lives."""
    slots = min(window, max_len) if window else max_len
    kvh, dh = cfg.n_kv_heads, cfg.dh
    return {
        "k": jnp.zeros((batch, kvh, slots, dh), dtype),
        "v": jnp.zeros((batch, kvh, slots, dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def attention_step(p: Params, x_t: jax.Array, cache: Params, cfg: ModelConfig,
                   pos, *, window=None, cross_kv=None):
    """One-token decode.  x_t: (B, 1, d); pos: scalar int32 current position,
    or (B,) int32 per-row positions (rows diverge after partial draft
    acceptance in batched speculative decoding)."""
    b = x_t.shape[0]
    dh, h, kvh = cfg.dh, cfg.n_heads, cfg.n_kv_heads
    if cross_kv is not None:
        q = (x_t @ p["wq"].astype(x_t.dtype))
        if "bq" in p:
            q = q + p["bq"].astype(x_t.dtype)
        q = q.reshape(b, 1, h, dh).transpose(0, 2, 1, 3)
        o = ops.decode_attention(q, cross_kv["k"], cross_kv["v"],
                                 cache_len=cross_kv["len"])
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
        return shard(o @ p["wo"].astype(x_t.dtype), "btd"), cache

    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1                              # (B,) positions
    posb = pos[:, None] if per_row else jnp.broadcast_to(pos, (b, 1))
    q, k, v = _project_qkv(p, x_t, cfg)
    if cfg.mrope_sections is not None:
        posb = jnp.broadcast_to(posb, (3, b, 1))
    q = apply_rope(q, posb, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, posb, cfg.rope_theta, cfg.mrope_sections)
    q = q.transpose(0, 2, 1, 3)                          # (B, H, 1, dh)
    k = k.transpose(0, 2, 1, 3)                          # (B, KVH, 1, dh)
    v = v.transpose(0, 2, 1, 3)
    slots = cache["k"].shape[2]
    slot = pos % slots                                   # ring-buffer write
    if per_row:
        rows = jnp.arange(b)
        ck = cache["k"].at[rows, :, slot].set(k[:, :, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, :, slot].set(v[:, :, 0].astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=2)
    live = jnp.minimum(pos + 1, slots)
    if window is None:
        o = ops.decode_attention(q, ck, cv, cache_len=pos + 1)
    else:
        # ring buffer: all slots < live are valid (eviction already happened
        # in place — content-movable semantics); order irrelevant to softmax.
        o = ops.decode_attention(q, ck, cv, cache_len=live)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    y = shard(o @ p["wo"].astype(x_t.dtype), "btd")
    return y, {"k": ck, "v": cv, "len": pos + 1}


# ---------------------------------------------------------------------------
# dense FFNs
# ---------------------------------------------------------------------------

def init_ffn(cfg: ModelConfig, key) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.ffn == "swiglu":
        return {"w_gate": _dense_init(ks[0], (d, f)),
                "w_in": _dense_init(ks[1], (d, f)),
                "w_out": _dense_init(ks[2], (f, d))}
    return {"w_in": _dense_init(ks[0], (d, f)),
            "w_out": _dense_init(ks[1], (f, d))}


def apply_ffn(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_in"].astype(dt))
    else:
        act = jax.nn.gelu if cfg.ffn == "gelu" else jax.nn.relu
        h = act(x @ p["w_in"].astype(dt))
    h = shard(h, "btf")
    return shard(h @ p["w_out"].astype(dt), "btd")


# ---------------------------------------------------------------------------
# MoE with CPM comparable-memory routing
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, e), scale=0.02),
        "expert_gate": _dense_init(ks[1], (e, d, f)),
        "expert_in": _dense_init(ks[2], (e, d, f)),
        "expert_out": _dense_init(ks[3], (e, f, d)),
    }


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig):
    """Top-k capacity routing.

    Routing mask via ``repro.cpm.reference.comparable.topk_mask`` — the paper's
    content-comparable memory: every token PE compares its expert scores
    against the broadcast k-th value concurrently (~1 cycle), replacing a
    serial arg-top-k.  Load statistics come from Rule-6 parallel counting.
    Dispatch/combine are scatter/gather so the expert dimension (sharded
    over "model" = expert parallelism) moves tokens with all-to-alls, not
    O(E) dense compute.

    Returns (y, aux_loss).
    """
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    t = b * s
    dt = x.dtype
    xt = x.reshape(t, d)
    scores = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)              # (T, E)

    mask = comparable.topk_mask(probs, k)                # CPM routing (T, E)

    # aux load-balance loss (Rule-6 parallel counter per expert)
    load = mask.astype(jnp.float32).mean(0)              # fraction routed
    importance = probs.mean(0)
    aux = cfg.moe.router_aux_weight * e * jnp.sum(load * importance)

    # slot-major routing: (T, k) expert ids, highest-prob first.
    # stop_gradient: routing indices carry no tangent (and this JAX build's
    # multi-operand sort JVP needs batched gathers it does not support).
    eidx = jnp.argsort(jnp.where(mask, -jax.lax.stop_gradient(probs), jnp.inf),
                       axis=-1)[:, :k]
    # NOTE: one-hot contractions instead of take_along_axis — this JAX build
    # (Trainium-modified) lacks operand_batching_dims on Gather/Scatter
    # dimension numbers, which batched take_along_axis grads require.
    ohk = jax.nn.one_hot(eidx, e, dtype=probs.dtype)     # (T, k, E)
    gates_k = jnp.einsum("tke,te->tk", ohk, probs)
    gates_k = gates_k / jnp.maximum(gates_k.sum(-1, keepdims=True), 1e-9)

    cap = max(int(cfg.moe.capacity_factor * t * k / e), 4)
    # queue position of each (token, slot) inside its expert (token order).
    # log-depth associative scan (the paper's §8 super-connectivity applied
    # to the prefix sum): jnp.cumsum would lower to a reduce-window whose
    # cost is O(T^2) in both the XLA cost model and naive lowerings.
    oh = ohk.reshape(t * k, e).astype(jnp.int32)
    pos_flat = jax.lax.associative_scan(jnp.add, oh, axis=0) - 1
    pos = jnp.sum(pos_flat * oh, axis=-1).reshape(t, k)
    keep = pos < cap                                     # overflow -> dropped

    # scatter-dispatch (E sharded over "model" => all-to-all movement)
    vals = jnp.where(keep[..., None], xt[:, None, :], 0).astype(dt)  # (T,k,d)
    sc_e = jnp.where(keep, eidx, e - 1)
    sc_c = jnp.where(keep, pos, cap - 1)
    expert_x = jnp.zeros((e, cap, d), dt).at[sc_e, sc_c].add(vals)
    expert_x = shard(expert_x, "ecd")

    hg = jnp.einsum("ecd,edf->ecf", expert_x, p["expert_gate"].astype(dt))
    hi = jnp.einsum("ecd,edf->ecf", expert_x, p["expert_in"].astype(dt))
    h = shard(jax.nn.silu(hg) * hi, "ecf")
    eo = jnp.einsum("ecf,efd->ecd", h, p["expert_out"].astype(dt))
    eo = shard(eo, "ecd")

    # gather-combine weighted by gates
    gathered = eo[sc_e, sc_c]                            # (T, k, d)
    w = jnp.where(keep, gates_k, 0.0).astype(dt)
    out = jnp.einsum("tkd,tk->td", gathered, w)
    return shard(out.reshape(b, s, d), "btd"), aux


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# ---------------------------------------------------------------------------

def init_rglru(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    w = cfg.rnn_width or d
    ks = jax.random.split(key, 6)
    # a_param initialized so a = sigmoid(a_param) in [0.9, 0.999]
    lo, hi = 0.9, 0.999
    u = jax.random.uniform(ks[4], (w,), jnp.float32, lo, hi)
    return {
        "wx": _dense_init(ks[0], (d, w)),                # branch input proj
        "wg": _dense_init(ks[1], (d, w)),                # gelu gate proj
        "wy": _dense_init(ks[2], (w, d)),
        "conv_w": _dense_init(ks[3], (cfg.conv_width, w), scale=0.1),
        "a_param": jnp.log(u / (1 - u)),
        "w_input_gate": _dense_init(ks[5], (w, w), scale=0.02) if False else
            jnp.zeros((2, w), jnp.float32),              # [input gate, rec gate] diag
    }


_RGLRU_C = 8.0


def _rglru_scan(x: jax.Array, a_param, gate_x, rec_x, h0=None):
    """h_t = a_t*h_{t-1} + sqrt(1-a_t^2)*(i_t*x_t)   via associative scan.

    The log-depth associative scan is the paper's §8 super-connectivity
    applied along the sequence: neighbor links at strides 1,2,4,…
    """
    log_a = -_RGLRU_C * jax.nn.softplus(a_param) * jax.nn.sigmoid(rec_x)
    a = jnp.exp(log_a)
    gated = x * jax.nn.sigmoid(gate_x)
    b = jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12)) * gated
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def rglru_fwd(p: Params, x: jax.Array, cfg: ModelConfig, with_cache=False):
    b, s, d = x.shape
    dt = x.dtype
    w = cfg.rnn_width or d
    branch = (x @ p["wx"].astype(dt)).astype(jnp.float32)       # (B,S,W)
    gate = jax.nn.gelu((x @ p["wg"].astype(dt)).astype(jnp.float32))
    # short depthwise causal conv (Griffin's temporal conv, width 4)
    conv = jnp.zeros_like(branch)
    for i in range(cfg.conv_width):
        shifted = jnp.pad(branch, ((0, 0), (i, 0), (0, 0)))[:, :s]
        conv = conv + shifted * p["conv_w"][i]
    ig = conv * jax.nn.sigmoid(p["w_input_gate"][0])
    rg = conv * jax.nn.sigmoid(p["w_input_gate"][1])
    h = _rglru_scan(conv, p["a_param"], ig, rg)
    y = (h.astype(dt) * gate.astype(dt)) @ p["wy"].astype(dt)
    y = shard(y, "btd")
    if not with_cache:
        return y
    cw = cfg.conv_width
    if s >= cw - 1:
        buf = branch[:, s - (cw - 1):]
    else:
        buf = jnp.pad(branch, ((0, 0), (cw - 1 - s, 0), (0, 0)))
    return y, {"h": h[:, -1].astype(jnp.float32), "conv_buf": buf}


def init_rglru_cache(cfg: ModelConfig, batch: int) -> Params:
    w = cfg.rnn_width or cfg.d_model
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv_buf": jnp.zeros((batch, cfg.conv_width - 1, w), jnp.float32)}


def rglru_step(p: Params, x_t: jax.Array, cache: Params, cfg: ModelConfig):
    b = x_t.shape[0]
    dt = x_t.dtype
    branch = (x_t[:, 0] @ p["wx"].astype(dt)).astype(jnp.float32)  # (B,W)
    gate = jax.nn.gelu((x_t[:, 0] @ p["wg"].astype(dt)).astype(jnp.float32))
    hist = jnp.concatenate([cache["conv_buf"], branch[:, None]], axis=1)
    # conv_w[i] multiplies the value i steps in the past; hist is oldest-first
    conv = jnp.einsum("bcw,cw->bw", hist[:, ::-1], p["conv_w"])
    ig = conv * jax.nn.sigmoid(p["w_input_gate"][0])
    rg = conv * jax.nn.sigmoid(p["w_input_gate"][1])
    log_a = -_RGLRU_C * jax.nn.softplus(p["a_param"]) * jax.nn.sigmoid(rg)
    a = jnp.exp(log_a)
    bterm = jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12)) * (conv * jax.nn.sigmoid(ig))
    h = a * cache["h"] + bterm
    y = ((h * gate).astype(dt) @ p["wy"].astype(dt))[:, None]
    return shard(y, "btd"), {"h": h, "conv_buf": hist[:, 1:]}


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix memory) — chunked-parallel training, O(1) decode
# ---------------------------------------------------------------------------

def init_mlstm(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    up = 2 * d
    h = cfg.n_heads
    dh = up // h
    ks = jax.random.split(key, 7)
    return {
        "w_up": _dense_init(ks[0], (d, up)),             # pre-up projection
        "w_up_gate": _dense_init(ks[1], (d, up)),
        # head-block-diagonal q/k/v (xLSTM's per-head projections)
        "wq": _dense_init(ks[2], (h, dh, dh), scale=1 / math.sqrt(dh)),
        "wk": _dense_init(ks[3], (h, dh, dh), scale=1 / math.sqrt(dh)),
        "wv": _dense_init(ks[4], (h, dh, dh), scale=1 / math.sqrt(dh)),
        "w_if": _dense_init(ks[5], (up, 2 * h), scale=0.02),  # input/forget gates
        "w_down": _dense_init(ks[6], (up, d)),
    }


def _mlstm_chunk_scan(q, k, v, log_f, log_i, chunk: int):
    """Chunkwise-parallel mLSTM.  q,k,v: (B,H,S,dh); gates: (B,H,S) logs <= 0.

    Hardware adaptation (DESIGN.md): sigmoid input gate (log_i <= 0) keeps
    every decay factor <= 1, so the chunkwise form is stable in fp32 without
    the m-stabilizer state.
    """
    b, h, s, dh = q.shape
    assert s % chunk == 0
    n = s // chunk
    q = q.reshape(b, h, n, chunk, dh)
    k = k.reshape(b, h, n, chunk, dh)
    v = v.reshape(b, h, n, chunk, dh)
    log_f = log_f.reshape(b, h, n, chunk)
    log_i = log_i.reshape(b, h, n, chunk)
    cum_f = jnp.cumsum(log_f, axis=-1)                   # (B,H,N,C)
    total_f = cum_f[..., -1:]

    # intra-chunk decay matrix D[i,j] = exp(cum_f_i - cum_f_j + log_i_j), j<=i
    di = cum_f[..., :, None] - cum_f[..., None, :] + log_i[..., None, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    dmat = jnp.where(mask, jnp.exp(di), 0.0)

    # inter-chunk state: C_n = exp(total_f) C_{n-1} + sum_j exp(total_f - cum_f_j + log_i_j) k_j v_j^T
    wk = jnp.exp(total_f - cum_f + log_i)[..., None] * k  # (B,H,N,C,dh)
    dC = jnp.einsum("bhncd,bhnce->bhnde", wk, v)          # (B,H,N,dh,dh)
    dnorm = jnp.sum(wk, axis=-2)                          # (B,H,N,dh)
    decay = jnp.exp(total_f[..., 0])                      # (B,H,N)

    def combine(c1, c2):
        a1, C1, n1 = c1
        a2, C2, n2 = c2
        return a1 * a2, C1 * a2[..., None, None] + C2, n1 * a2[..., None] + n2

    _, Ccum, ncum = jax.lax.associative_scan(
        combine, (decay, dC, dnorm), axis=2)
    # state *before* each chunk
    Cprev = jnp.concatenate([jnp.zeros_like(Ccum[:, :, :1]), Ccum[:, :, :-1]], 2)
    nprev = jnp.concatenate([jnp.zeros_like(ncum[:, :, :1]), ncum[:, :, :-1]], 2)

    qs = q * jnp.exp(cum_f)[..., None]
    inter = jnp.einsum("bhncd,bhnde->bhnce", qs, Cprev)
    inter_n = jnp.einsum("bhncd,bhnd->bhnc", qs, nprev)
    intra = jnp.einsum("bhncd,bhnjd->bhncj", q, k) * dmat
    out = inter + jnp.einsum("bhncj,bhnjd->bhncd", intra, v)
    norm = inter_n + jnp.sum(intra, -1)
    out = out / jnp.maximum(jnp.abs(norm), 1.0)[..., None]
    final_state = (Ccum[:, :, -1], ncum[:, :, -1])
    return out.reshape(b, h, s, dh), final_state


def mlstm_fwd(p: Params, x: jax.Array, cfg: ModelConfig, with_cache=False,
              chunk: int = 256):
    b, s, d = x.shape
    dt = x.dtype
    h = cfg.n_heads
    up = p["w_up"].shape[1]
    dh = up // h
    z = shard(x @ p["w_up"].astype(dt), "btf")            # (B,S,up)
    gate = jax.nn.silu(x @ p["w_up_gate"].astype(dt))
    zh = shard(z.reshape(b, s, h, dh).transpose(0, 2, 1, 3), "bhsd")
    q = shard(jnp.einsum("bhsd,hde->bhse", zh, p["wq"].astype(dt)), "bhsd")
    k = shard(jnp.einsum("bhsd,hde->bhse", zh, p["wk"].astype(dt)), "bhsd") / math.sqrt(dh)
    v = shard(jnp.einsum("bhsd,hde->bhse", zh, p["wv"].astype(dt)), "bhsd")
    gif = (z @ p["w_if"].astype(dt)).astype(jnp.float32)  # (B,S,2H)
    log_i = jax.nn.log_sigmoid(gif[..., :h]).transpose(0, 2, 1)
    log_f = jax.nn.log_sigmoid(gif[..., h:]).transpose(0, 2, 1)
    c = min(chunk, s)
    out, (C, nrm) = _mlstm_chunk_scan(q.astype(jnp.float32), k.astype(jnp.float32),
                                      v.astype(jnp.float32), log_f, log_i, c)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, up).astype(dt)
    y = shard((out * gate) @ p["w_down"].astype(dt), "btd")
    if not with_cache:
        return y
    return y, {"C": C, "n": nrm, "len": jnp.asarray(s, jnp.int32)}


def init_mlstm_cache(cfg: ModelConfig, batch: int) -> Params:
    up = 2 * cfg.d_model
    h = cfg.n_heads
    dh = up // h
    return {"C": jnp.zeros((batch, h, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, h, dh), jnp.float32),
            "len": jnp.zeros((), jnp.int32)}


def mlstm_step(p: Params, x_t: jax.Array, cache: Params, cfg: ModelConfig):
    b = x_t.shape[0]
    dt = x_t.dtype
    h = cfg.n_heads
    up = p["w_up"].shape[1]
    dh = up // h
    z = x_t[:, 0] @ p["w_up"].astype(dt)
    gate = jax.nn.silu(x_t[:, 0] @ p["w_up_gate"].astype(dt))
    zh = z.reshape(b, h, dh)
    q = jnp.einsum("bhd,hde->bhe", zh, p["wq"].astype(dt)).astype(jnp.float32)
    k = (jnp.einsum("bhd,hde->bhe", zh, p["wk"].astype(dt)) / math.sqrt(dh)).astype(jnp.float32)
    v = jnp.einsum("bhd,hde->bhe", zh, p["wv"].astype(dt)).astype(jnp.float32)
    gif = (z @ p["w_if"].astype(dt)).astype(jnp.float32)
    i_g = jnp.exp(jax.nn.log_sigmoid(gif[..., :h]))[..., None]       # (B,H,1)
    f_g = jnp.exp(jax.nn.log_sigmoid(gif[..., h:]))[..., None]
    C = f_g[..., None] * cache["C"] + i_g[..., None] * k[..., :, None] * v[..., None, :]
    nrm = f_g * cache["n"] + i_g * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, nrm)), 1.0)
    out = (num / den[..., None]).reshape(b, up).astype(dt)
    y = ((out * gate) @ p["w_down"].astype(dt))[:, None]
    return shard(y, "btd"), {"C": C, "n": nrm, "len": cache["len"] + 1}


# ---------------------------------------------------------------------------
# sLSTM (xLSTM scalar memory, stabilized exponential gating)
# ---------------------------------------------------------------------------

def init_slstm(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 3)
    return {
        "wx": _dense_init(ks[0], (d, 4 * d)),            # z, i, f, o pre-acts
        "rec_w": _dense_init(ks[1], (h, dh, 4 * dh), scale=0.02),
        "w_down": _dense_init(ks[2], (d, d)),
    }


def _slstm_cell(p, cfg, x_pre, state):
    """x_pre: (B, 4D) input pre-activations; state: (c, n, h, m) each (B,H,dh)."""
    b = x_pre.shape[0]
    hh = cfg.n_heads
    d = cfg.d_model
    dh = d // hh
    c, n, hprev, m = state
    rec = jnp.einsum("bhd,hdk->bhk", hprev, p["rec_w"].astype(hprev.dtype))
    pre = x_pre.reshape(b, hh, 4 * dh) + rec
    z = jnp.tanh(pre[..., :dh])
    i_l = pre[..., dh:2 * dh]                             # log-space input gate
    f_l = jax.nn.log_sigmoid(pre[..., 2 * dh:3 * dh])     # log forget
    o = jax.nn.sigmoid(pre[..., 3 * dh:])
    m_new = jnp.maximum(f_l + m, i_l)
    i_g = jnp.exp(i_l - m_new)
    f_g = jnp.exp(f_l + m - m_new)
    c_new = f_g * c + i_g * z
    n_new = f_g * n + i_g
    h_new = o * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, h_new, m_new)


def slstm_fwd(p: Params, x: jax.Array, cfg: ModelConfig, with_cache=False):
    b, s, d = x.shape
    dt = x.dtype
    hh = cfg.n_heads
    dh = d // hh
    x_pre = (x @ p["wx"].astype(dt)).astype(jnp.float32)   # (B,S,4D)
    rec_w = p["rec_w"].astype(jnp.float32)

    def local_scan(x_pre, rec_w):
        """Batch-local recurrence.  Run under shard_map when a mesh is
        active: the 4096-step scan must be device-local — any re-sharding
        freedom inside the loop costs one collective *per timestep*."""
        bl = x_pre.shape[0]
        init = tuple(jnp.zeros((bl, hh, dh), jnp.float32) for _ in range(3)) + (
            jnp.full((bl, hh, dh), -1e30, jnp.float32),)
        pp = {"rec_w": rec_w}

        def step(state, xp):
            new = _slstm_cell(pp, cfg, xp, state)
            return new, new[2]

        state, hs = jax.lax.scan(step, init, x_pre.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2, 3).reshape(bl, x_pre.shape[1], d), state

    from repro.distributed.sharding import current_ctx
    ctx = current_ctx()
    if ctx.mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        dp = ctx.dp
        local_scan = shard_map(
            local_scan, mesh=ctx.mesh,
            in_specs=(P(dp, None, None), P(None, None, None)),
            out_specs=(P(dp, None, None),
                       tuple(P(dp, None, None) for _ in range(4))),
            check_vma=False)
    out, state = local_scan(x_pre, rec_w)
    y = shard(out.astype(dt) @ p["w_down"].astype(dt), "btd")
    if not with_cache:
        return y
    return y, {"c": state[0], "n": state[1], "h": state[2], "m": state[3]}


def init_slstm_cache(cfg: ModelConfig, batch: int) -> Params:
    hh = cfg.n_heads
    dh = cfg.d_model // hh
    z = jnp.zeros((batch, hh, dh), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, hh, dh), -1e30, jnp.float32)}


def slstm_step(p: Params, x_t: jax.Array, cache: Params, cfg: ModelConfig):
    dt = x_t.dtype
    x_pre = (x_t[:, 0] @ p["wx"].astype(dt)).astype(jnp.float32)
    state = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_cell(p, cfg, x_pre, state)
    b = x_t.shape[0]
    out = h.reshape(b, -1).astype(dt)
    y = (out @ p["w_down"].astype(dt))[:, None]
    return shard(y, "btd"), {"c": c, "n": n, "h": h, "m": m}

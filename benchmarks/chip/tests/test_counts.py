"""The count functions and the peaks table against hand counts."""

import json

import pytest

import counts
import harness
from peaks import peaks


def cfg(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


def test_granite_8b_per_token():
    c = cfg("granite-8b-code.L8")
    # q, k, v, o: 4096 x (32 + 2 * 8) * 128 and 4096 x 4096; SwiGLU: three
    # 4096 x 14336 matrices; two FLOPs per multiply-add
    layer = 2 * (4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336)
    assert counts.layer_matmul_flops(c) == layer == 436_207_616
    assert counts.logits_flops(c) == 2 * 4096 * 49152
    # a decode token at 100 keys: 8 layers x 32 heads x 128 x 4 per key
    assert counts.decode_flops(c, 100) == (
        8 * layer + 8 * 32 * 128 * 4 * 100 + 2 * 4096 * 49152)
    # a 128-token prompt: causal keys 1 + 2 + ... + 128
    assert counts.prefill_flops(c, 128) == (
        128 * 8 * layer + 8 * 32 * 128 * 4 * (128 * 129 // 2)
        + 2 * 4096 * 49152)


def test_granite_moe_routes_top_k_only():
    c = cfg("granite-3.0-1b-a400m")
    attn = 2 * (1024 * (16 + 16) * 64 + 1024 * 1024)
    ffn = 2 * 1024 * 32 + 8 * 2 * 3 * 1024 * 512   # router + 8 of 32 experts
    assert counts.layer_matmul_flops(c) == attn + ffn == 31_522_816
    assert counts.decode_flops(c, 1) == (
        24 * (attn + ffn) + 24 * 16 * 64 * 4 + 2 * 1024 * 49155)


def test_kernel_costs():
    flops, nbytes = counts.flash_attention_cost(1, 32, 8, 128, 128)
    assert flops == 4 * 32 * 128 * (128 * 129 / 2)
    assert nbytes == 2 * 128 * 128 * (32 + 32 + 8 + 8)
    assert counts.gather_rows_bytes(2048, 16) == 2 * 2048 * 16 * 4 + 4 * 2048
    assert counts.scatter_rows_bytes(2048, 100, 16) == (
        2 * 2048 * 16 * 4 + 4 * 100)
    t, bound = counts.roofline_s(197e12, 1.0, peaks("TPU v5 lite"))
    assert (t, bound) == (1.0, "compute")
    t, bound = counts.roofline_s(1.0, 819e9, peaks("TPU v5 lite"))
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")

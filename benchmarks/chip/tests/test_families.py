"""Golden values of the Granite family: what the harness drew, built and
counted before the family modules existed, recorded on that code and
written here as literals.  The move into ``families/granite.py`` keeps
every one exactly: the program's ``ModelConfig`` field by field, each
canonical weight at rehearsal size (name, shape, float64 sum of its
float32 values, first 8 values), the program's parameter tree drawn from
it, and the useful FLOPs at full widths."""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

import families
import harness
import stats
import weights
from traffic import load

SEED = 20260417
CONFIGS = ["granite-8b-code.L8", "granite-3.0-1b-a400m"]


def config(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


def tiny(name):
    return harness.rehearsal_sizes(config(name), load("code-chat"))[0]


def fields(cfg):
    return dataclasses.asdict(harness.model_config(cfg))


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config_field_by_field(name):
    assert config(name)["family"] == "granite"
    assert fields(config(name)) == GOLDEN[name]["model_config"]
    assert fields(tiny(name)) == GOLDEN[name]["model_config_tiny"]


@pytest.mark.parametrize("name", CONFIGS)
def test_canonical_weights_at_rehearsal_size(name):
    cfg = tiny(name)
    w = jax.jit(lambda k: weights.canonical(cfg, k))(weights.key(SEED))
    got = {n: (x.shape, float(np.asarray(x, np.float64).sum()),
               [float(v) for v in np.asarray(x).ravel()[:8]])
           for n, x in w.items()}
    want = {n: (shape, total, first)
            for n, (shape, total, first) in GOLDEN[name]["weights"].items()}
    assert got == want


@pytest.mark.parametrize("name", CONFIGS)
def test_program_params_at_rehearsal_size(name):
    from repro.models import lm
    cfg = tiny(name)
    vp = lm.padded_vocab(harness.model_config(cfg))
    p = jax.jit(lambda k: families.of(cfg).program_params(
        cfg, weights.canonical(cfg, k), vp))(weights.key(SEED))
    got = {"/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                    for e in path):
           (x.shape, float(np.asarray(x, np.float64).sum()))
           for path, x in jax.tree_util.tree_leaves_with_path(p)}
    assert got == GOLDEN[name]["program"]


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_at_full_widths(name):
    cfg, fam = config(name), families.of(config(name))
    want = GOLDEN[name]
    assert {s: fam.prefill_flops(cfg, s) for s in (128, 256, 512, 1024)} \
        == want["prefill_flops"]
    assert {k: fam.decode_flops(cfg, k) for k in (129, 2048)} \
        == want["decode_flops"]


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_count_with_the_family(name):
    # ``run.py`` hands the readers the family as ``run.counts``: a
    # 128-token prompt's first token stands for its prefill, the second
    # for a decode step at 129 keys
    cfg = config(name)
    rec = types.SimpleNamespace(prompt=np.zeros(128, np.int32),
                                times=[1.0, 1.1])
    run = types.SimpleNamespace(counts=families.of(cfg), cfg=cfg,
                                win=types.SimpleNamespace(recs=[rec]))
    assert stats.served_flops(run, 0.0, 2.0) == (
        GOLDEN[name]["prefill_flops"][128] + GOLDEN[name]["decode_flops"][129])


# recorded on the parent of the family modules, at seed SEED
GOLDEN = {
    "granite-8b-code.L8": {
        "model_config": {
            "name": "granite-8b-code.L8",
            "family": "dense",
            "n_layers": 8,
            "d_model": 4096,
            "n_heads": 32,
            "n_kv_heads": 8,
            "d_ff": 14336,
            "vocab_size": 49152,
            "head_dim": 128,
            "moe": None,
            "qkv_bias": False,
            "norm": "rms",
            "norm_eps": 1e-05,
            "rope_theta": 10000.0,
            "tie_embeddings": False,
            "pattern": ("attn",),
            "window": 0,
            "ffn": "swiglu",
            "enc_dec": False,
            "n_enc_layers": 0,
            "mrope_sections": None,
            "rnn_width": 0,
            "conv_width": 4,
        },
        "model_config_tiny": {
            "name": "granite-8b-code.L8",
            "family": "dense",
            "n_layers": 2,
            "d_model": 64,
            "n_heads": 4,
            "n_kv_heads": 2,
            "d_ff": 96,
            "vocab_size": 256,
            "head_dim": 16,
            "moe": None,
            "qkv_bias": False,
            "norm": "rms",
            "norm_eps": 1e-05,
            "rope_theta": 10000.0,
            "tie_embeddings": False,
            "pattern": ("attn",),
            "window": 0,
            "ffn": "swiglu",
            "enc_dec": False,
            "n_enc_layers": 0,
            "mrope_sections": None,
            "rnn_width": 0,
            "conv_width": 4,
        },
        "weights": {
            "embed": ((256, 64), -0.20393353878262133, [
                0.010313200764358044, 0.030177580192685127,
                0.0062037245370447636, -0.005929066799581051,
                -0.03757236525416374, 0.016847986727952957,
                0.014771527610719204, 0.018828604370355606,
            ]),
            "final_norm": ((64,), 62.16350847482681, [
                0.8479552865028381, 0.9941915273666382,
                1.0255165100097656, 1.0376836061477661,
                1.0727101564407349, 0.9942600727081299,
                0.82706618309021, 1.0591005086898804,
            ]),
            "norm1": ((2, 64), 125.70419269800186, [
                1.1470849514007568, 0.9631853103637695,
                0.9625764489173889, 0.9907433390617371,
                0.8710876703262329, 1.0096254348754883,
                1.0565474033355713, 0.9415494799613953,
            ]),
            "norm2": ((2, 64), 126.62448745965958, [
                0.9499024748802185, 0.9110494256019592,
                1.0114963054656982, 0.8499085903167725,
                0.9873109459877014, 0.8558441400527954,
                0.9793758988380432, 1.2127301692962646,
            ]),
            "unembed": ((256, 64), 1.402285680546811, [
                -0.00934880506247282, 0.03257034346461296,
                0.028882725164294243, 0.01245009433478117,
                0.01859331876039505, 0.010782144032418728,
                -0.016959184780716896, 0.04054967314004898,
            ]),
            "w_down": ((2, 96, 64), 14.141757681094532, [
                0.16244055330753326, -0.14051851630210876,
                0.14912991225719452, -0.07378579676151276,
                0.05266909673810005, 0.08543605357408524,
                -0.049468036741018295, -0.14551234245300293,
            ]),
            "w_gate": ((2, 64, 96), -7.067857965626899, [
                -0.013518867082893848, -0.14257530868053436,
                0.07090204209089279, 0.186599001288414,
                -0.14699436724185944, 0.06676232814788818,
                0.0558156818151474, 0.08284087479114532,
            ]),
            "w_up": ((2, 64, 96), -5.592024403663345, [
                -0.002421681536361575, -0.09738459438085556,
                -0.08608552068471909, 0.014341320842504501,
                0.07691606879234314, -0.1894240379333496,
                0.03428995609283447, -0.13502953946590424,
            ]),
            "wk": ((2, 64, 32), 1.4083415524419252, [
                -0.11926723271608353, 0.05919268727302551,
                -0.009581015445291996, -0.04403525963425636,
                -0.0008059581741690636, -0.0847620964050293,
                0.004530205857008696, 0.03779367730021477,
            ]),
            "wo": ((2, 64, 64), -13.595465548690754, [
                -0.043716561049222946, -0.02986135520040989,
                -0.011482144705951214, -0.1444251984357834,
                0.049041420221328735, -0.13807907700538635,
                -0.02278078719973564, 0.16802214086055756,
            ]),
            "wq": ((2, 64, 64), -13.837423096316343, [
                -0.06986047327518463, 0.08978927880525589,
                0.020977959036827087, 0.14583128690719604,
                0.09530504792928696, 0.06726883351802826,
                0.20373137295246124, -0.11859149485826492,
            ]),
            "wv": ((2, 64, 32), -3.1798157155003537, [
                0.18050852417945862, -0.04639684781432152,
                -0.008170478977262974, 0.12239057570695877,
                -0.24715399742126465, -0.0073989322409033775,
                -0.06127355992794037, 0.20976229012012482,
            ]),
        },
        "program": {
            "blocks/0/attn/wk": ((2, 64, 32), 1.4083415524419252),
            "blocks/0/attn/wo": ((2, 64, 64), -13.595465548690754),
            "blocks/0/attn/wq": ((2, 64, 64), -13.837423096316343),
            "blocks/0/attn/wv": ((2, 64, 32), -3.1798157155003537),
            "blocks/0/ffn/w_gate": ((2, 64, 96), -7.067857965626899),
            "blocks/0/ffn/w_in": ((2, 64, 96), -5.592024403663345),
            "blocks/0/ffn/w_out": ((2, 96, 64), 14.141757681094532),
            "blocks/0/norm1/scale": ((2, 64), 125.70419269800186),
            "blocks/0/norm2/scale": ((2, 64), 126.62448745965958),
            "emb": ((512, 64), -0.20393353878262133),
            "final_norm/scale": ((64,), 62.16350847482681),
            "unemb": ((512, 64), 1.402285680546811),
        },
        "prefill_flops": {128: 448161382400.0, 256: 898067595264.0,
                          512: 1804322471936.0, 1024: 3642602029056.0},
        "decode_flops": {129: 3909222400.0, 2048: 4160749568.0},
    },
    "granite-3.0-1b-a400m": {
        "model_config": {
            "name": "granite-3.0-1b-a400m",
            "family": "moe",
            "n_layers": 24,
            "d_model": 1024,
            "n_heads": 16,
            "n_kv_heads": 8,
            "d_ff": 512,
            "vocab_size": 49155,
            "head_dim": 64,
            "moe": {"n_experts": 32, "top_k": 8, "capacity_factor": 4.0,
                    "router_aux_weight": 0.01},
            "qkv_bias": False,
            "norm": "rms",
            "norm_eps": 1e-05,
            "rope_theta": 10000.0,
            "tie_embeddings": True,
            "pattern": ("attn",),
            "window": 0,
            "ffn": "moe",
            "enc_dec": False,
            "n_enc_layers": 0,
            "mrope_sections": None,
            "rnn_width": 0,
            "conv_width": 4,
        },
        "model_config_tiny": {
            "name": "granite-3.0-1b-a400m",
            "family": "moe",
            "n_layers": 2,
            "d_model": 64,
            "n_heads": 4,
            "n_kv_heads": 2,
            "d_ff": 96,
            "vocab_size": 256,
            "head_dim": 16,
            "moe": {"n_experts": 4, "top_k": 2, "capacity_factor": 2.0,
                    "router_aux_weight": 0.01},
            "qkv_bias": False,
            "norm": "rms",
            "norm_eps": 1e-05,
            "rope_theta": 10000.0,
            "tie_embeddings": True,
            "pattern": ("attn",),
            "window": 0,
            "ffn": "moe",
            "enc_dec": False,
            "n_enc_layers": 0,
            "mrope_sections": None,
            "rnn_width": 0,
            "conv_width": 4,
        },
        "weights": {
            "e_down": ((2, 4, 96, 64), -12.689422004008804, [
                0.05002757906913757, 0.1397702544927597,
                0.03017398528754711, -0.028841780498623848,
                -0.16638442873954773, 0.08109181374311447,
                0.07131282240152359, 0.09031301736831665,
            ]),
            "e_gate": ((2, 4, 64, 96), -38.7820530399556, [
                -0.17230935394763947, -0.00692990655079484,
                0.030414698645472527, 0.04486430436372757,
                0.08600884675979614, -0.006848139222711325,
                -0.19154265522956848, 0.0701269581913948,
            ]),
            "e_up": ((2, 4, 64, 96), -33.7436486528768, [
                0.16743996739387512, -0.043834224343299866,
                -0.044556066393852234, -0.011042950674891472,
                -0.14880426228046417, 0.011482790112495422,
                0.06713050603866577, -0.06936459988355637,
            ]),
            "embed": ((256, 64), 0.5454285913501735, [
                -0.01001950353384018, -0.017790116369724274,
                0.002299267565831542, -0.0300182793289423,
                -0.002537813503295183, -0.02883117087185383,
                -0.004124823957681656, 0.04254604130983353,
            ]),
            "final_norm": ((64,), 64.87222343683243, [
                0.9532559514045715, 1.1628516912460327,
                1.1444135904312134, 1.0622504949569702,
                1.0929665565490723, 1.053910732269287,
                0.9152040481567383, 1.202748417854309,
            ]),
            "norm1": ((2, 64), 128.25478130578995, [
                1.1817618608474731, 0.8481714725494385,
                1.162971019744873, 0.92352694272995,
                1.0543146133422852, 1.0888900756835938,
                0.949015736579895, 0.8417859077453613,
            ]),
            "norm2": ((2, 64), 128.55597764253616, [
                0.9886671900749207, 0.876940906047821,
                1.0597615242004395, 1.1673234701156616,
                0.872797966003418, 1.0562338829040527,
                1.0469402074813843, 1.0699838399887085,
            ]),
            "router": ((2, 64, 4), -1.4761086424114183, [
                -0.002421681536361575, -0.09738459438085556,
                -0.08608552068471909, 0.014341320842504501,
                0.07691606879234314, -0.1894240379333496,
                0.03428995609283447, -0.13502953946590424,
            ]),
            "wk": ((2, 64, 32), 1.4083415524419252, [
                -0.11926723271608353, 0.05919268727302551,
                -0.009581015445291996, -0.04403525963425636,
                -0.0008059581741690636, -0.0847620964050293,
                0.004530205857008696, 0.03779367730021477,
            ]),
            "wo": ((2, 64, 64), -13.595465548690754, [
                -0.043716561049222946, -0.02986135520040989,
                -0.011482144705951214, -0.1444251984357834,
                0.049041420221328735, -0.13807907700538635,
                -0.02278078719973564, 0.16802214086055756,
            ]),
            "wq": ((2, 64, 64), -13.837423096316343, [
                -0.06986047327518463, 0.08978927880525589,
                0.020977959036827087, 0.14583128690719604,
                0.09530504792928696, 0.06726883351802826,
                0.20373137295246124, -0.11859149485826492,
            ]),
            "wv": ((2, 64, 32), -3.1798157155003537, [
                0.18050852417945862, -0.04639684781432152,
                -0.008170478977262974, 0.12239057570695877,
                -0.24715399742126465, -0.0073989322409033775,
                -0.06127355992794037, 0.20976229012012482,
            ]),
        },
        "program": {
            "blocks/0/attn/wk": ((2, 64, 32), 1.4083415524419252),
            "blocks/0/attn/wo": ((2, 64, 64), -13.595465548690754),
            "blocks/0/attn/wq": ((2, 64, 64), -13.837423096316343),
            "blocks/0/attn/wv": ((2, 64, 32), -3.1798157155003537),
            "blocks/0/ffn/expert_gate": ((2, 4, 64, 96), -38.7820530399556),
            "blocks/0/ffn/expert_in": ((2, 4, 64, 96), -33.7436486528768),
            "blocks/0/ffn/expert_out": ((2, 4, 96, 64), -12.689422004008804),
            "blocks/0/ffn/router": ((2, 64, 4), -1.4761086424114183),
            "blocks/0/norm1/scale": ((2, 64), 128.25478130578995),
            "blocks/0/norm2/scale": ((2, 64), 128.55597764253616),
            "emb": ((512, 64), 0.5454285913501735),
            "final_norm/scale": ((64,), 64.87222343683243),
        },
        "prefill_flops": {128: 97750358016.0, 256: 197010659328.0,
                          512: 400363100160.0, 1024: 826395334656.0},
        "decode_flops": {129: 869898240.0, 2048: 1058543616.0},
    },
}

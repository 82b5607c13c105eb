"""FLOP and byte counts against hand counts at published widths."""
from __future__ import annotations

import json

import pytest

from bench.lib.spec import family_module
from benchtools import MODELS, ROOT

SC2, MB2 = MODELS["starcoder2-3b"], MODELS["mamba2-780m"]
sc2 = family_module("counts", "starcoder2")
mb2 = family_module("counts", "mamba2")

# starcoder2-3b, per layer: q 3072*3072 + k,v 2*3072*256 + o 3072*3072 +
# mlp 2*3072*12288 = 95,944,704 weights; 30 layers + 49152*3072 embedding
# = 3.029e9 parameters (the published 3B)
SC2_LIN = 95_944_704
SC2_WEIGHT_BYTES = 30 * (2 * SC2_LIN + 4 * (4 * 3072 + 12288 + 3072)) \
    + 2 * 49152 * 3072                                   # 6,061,989,888
# mamba2-780m, per layer: in_proj 1536*6448, out_proj 3072*1536
MB2_MATS = 1536 * 6448 + 3072 * 1536                     # 14,622,720


def test_parameter_totals_match_the_published_sizes():
    assert 30 * SC2_LIN + 49152 * 3072 == 3_029_336_064
    assert 48 * MB2_MATS + 50280 * 1536 == 779_120_640


def test_sc2_prefill_one_token():
    flops, byts = sc2.prefill(SC2, 0, 1)
    # 30 layers x (2 per weight + 4*24*128 for one attended position),
    # plus the last token's logits 2*3072*49152
    assert flops == 30 * (2 * SC2_LIN + 4 * 24 * 128) + 2 * 3072 * 49152
    assert flops == 6_059_040_768
    # weights once + K and V of one position in 30 layers + one embedding row
    assert byts == SC2_WEIGHT_BYTES + 30 * 2 * 2 * 128 * 2 + 2 * 3072
    assert byts == 6_062_026_752


def test_sc2_prefill_counts_only_real_positions():
    f16, b16 = sc2.prefill(SC2, 100, 16)
    attended = sum(range(101, 117))                     # positions 100..115
    assert f16 == 16 * 30 * 2 * SC2_LIN + 30 * 4 * 24 * 128 * attended \
        + 2 * 3072 * 49152
    assert b16 == SC2_WEIGHT_BYTES + 30 * 2 * 2 * 128 * 2 * 116 + 2 * 16 * 3072


@pytest.mark.parametrize("counts,spec", [(sc2, SC2), (mb2, MB2)])
def test_prefill_chunk_before_the_prompt_end_needs_no_logits(counts, spec):
    d = int(spec.get("hidden_size") or spec["d_model"])
    v = int(spec["vocab_size"])
    f_last, b_last = counts.prefill(spec, 32, 16)
    f_mid, b_mid = counts.prefill(spec, 32, 16, last=False)
    assert f_last - f_mid == 2 * d * v                # no logits
    assert b_last - b_mid == 2 * d * v                # no whole table read


def test_sc2_decode_reads_weights_once_per_step():
    f, b = sc2.decode(SC2, [(10, 2), (500, 1)], steps=2)
    toks = [11, 12, 501]                                 # context per token
    per_tok = 30 * 2 * SC2_LIN + 2 * 3072 * 49152
    assert f == sum(per_tok + 30 * 4 * 24 * 128 * c for c in toks)
    assert b == 2 * SC2_WEIGHT_BYTES + sum(
        30 * 2 * 2 * 128 * 2 * c + 2 * 3072 for c in toks)


def test_mb2_decode_one_row_one_step():
    f, b = mb2.decode(MB2, [(100, 1)], steps=1)
    # 48 x (2 per weight + 2*4 conv taps * 3328 channels + 5*48*64*128
    # state) + logits 2*1536*50280
    assert f == 48 * (2 * MB2_MATS + 8 * 3328 + 5 * 48 * 64 * 128) \
        + 2 * 1536 * 50280
    assert f == 1_653_891_072
    state = 2 * 48 * (4 * 48 * 64 * 128 + 2 * 3 * 3328)
    weights = 48 * (2 * MB2_MATS + 4 * (4 * 3328 + 3328 + 3 * 48 + 3072
                                        + 1536)) + 2 * 50280 * 1536
    assert b == weights + state + 2 * 1536 == 1_715_263_488


def test_mb2_prefill_state_once_per_call():
    f1, b1 = mb2.prefill(MB2, 0, 16)
    f2, b2 = mb2.prefill(MB2, 512, 16)
    assert f1 == f2 and b1 == b2                         # no context growth
    assert f1 == pytest.approx(16 * 1_499_430_912 + 2 * 1536 * 50280)


@pytest.mark.parametrize("counts,spec", [(sc2, SC2), (mb2, MB2)])
def test_decode_step_is_bandwidth_bound_on_v5e(counts, spec):
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]
    f, b = counts.decode(spec, [(512, 1)] * 32, steps=1)
    assert b / peaks["hbm_bytes_per_s"] > f / peaks["flops_per_s"]

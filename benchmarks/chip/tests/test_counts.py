"""Operation and byte counts against values worked out by hand, for one
decode and one prefill shape of Qwen2.5-3B (the cell's configuration file)
and of a windowed GQA shape (Phi-3-medium's widths, a 2047-key window:
``fixtures/phi3_medium_shape.json``), each reached through the counts of
the configuration's reference module."""
import json
from pathlib import Path

import pytest

from harness import counts, spec

CONFIGS = Path(__file__).parents[1] / "configs"
FIXTURES = Path(__file__).with_name("fixtures")
QWEN_CONF = json.loads((CONFIGS / "qwen2_5_3b.json").read_text())
PHI_CONF = json.loads((FIXTURES / "phi3_medium_shape.json").read_text())
REF = spec.reference_module(QWEN_CONF)
QWEN = REF.counts_of(QWEN_CONF)
PHI = spec.reference_module(PHI_CONF).counts_of(PHI_CONF)


def test_counts_are_built_from_the_configuration_file():
    assert QWEN == REF.Dims(D=2048, H=16, K=2, hd=128, F=11008, V=151936,
                            L=36, r=16)            # use_sliding_window false
    assert PHI == REF.Dims(D=5120, H=40, K=10, hd=128, F=17920, V=32064,
                           L=10, r=16, window=2047)


def test_per_token_flops_qwen():
    # 2 * 36 * (q 2048*2048 + k,v 2*2048*256 + o 2048*2048 + mlp 3*2048*11008)
    assert REF.dense_flops(QWEN) == 5_549_064_192
    # 2 * 36 * 16 * (q 4096 + k,v 2*2304 + o 4096)
    assert REF.lora_flops(QWEN) == 14_745_600
    assert REF.head_flops(QWEN) == 622_329_856           # 2 * 2048 * 151936
    assert REF.attn_flops(QWEN, 99) == 4 * 36 * 16 * 128 * 100


def test_per_token_flops_phi():
    # 2 * 10 * (5120*5120 + 2*5120*1280 + 5120*5120 + 3*5120*17920)
    assert REF.dense_flops(PHI) == 6_815_744_000
    assert REF.lora_flops(PHI) == 10_649_600         # 320 * 33280
    assert REF.head_flops(PHI) == 328_335_360        # 2 * 5120 * 32064
    # the window caps the context at 2047 keys
    assert REF.attn_flops(PHI, 5000) == 4 * 10 * 40 * 128 * 2047


def test_decode_call_qwen():
    # rows at 100 (4 tokens kept) and 2000 (1 kept): contexts 101..104, 2001
    per_tok = 5_549_064_192 + 14_745_600 + 622_329_856
    want = 5 * per_tok + 294_912 * (101 + 102 + 103 + 104 + 2001)
    assert REF.decode_model_flops(QWEN, [100, 2000], [4, 1]) == want
    assert QWEN.decode_flops([100, 2000], [4, 1]) == want
    fl, by = REF.paged_decode_call(QWEN, [100, 2000])
    assert fl == 4 * 16 * 128 * (101 + 2001) == 17_219_584
    # K and V of 2102 positions over 2 heads, q and o of 16 heads, bf16
    assert by == 2 * (2 * 2 * 128 * 2102 + 2 * 2 * 16 * 128) == 2_168_832
    # a decode step runs the kernel once in each of the 36 layers
    assert QWEN.paged_decode_step([100, 2000]) == (36 * 17_219_584,
                                                   36 * 2_168_832)


def test_sgmv_call_qwen():
    fl, by = counts.sgmv_call(rows=32, adapters=20, din=2048, dout=2048,
                              rank=QWEN.lora_rank)
    assert fl == 2 * 32 * 16 * 4096 == 4_194_304
    assert by == 2 * (32 * 4096 + 20 * 16 * 4096) == 2_883_584
    # q, k, v, o in every layer
    assert QWEN.lora_sites() == [(2048, 2048, 36), (2048, 256, 36),
                                 (2048, 256, 36), (2048, 2048, 36)]


def test_prefill_phi():
    # positions 256..299 computed (256 covered by the prefix cache)
    want = 44 * (6_815_744_000 + 10_649_600) \
        + 4 * 10 * 40 * 128 * sum(range(257, 301)) + 328_335_360
    assert REF.prefill_model_flops(PHI, 300, 256) == want
    assert PHI.prefill_flops(300, 256) == want
    assert sum(range(257, 301)) == 12_254
    # a chunk at 1920..2047: contexts 1921..2046, then 2047 twice
    fl, by = REF.paged_prefill_call(PHI, [(1920, 128)])
    assert fl == 20480 * 254_015 == 5_202_227_200
    assert by == 2 * (2 * 10 * 128 * 2048 + 2 * 128 * 40 * 128) == 13_107_200
    assert PHI.paged_prefill_step([(1920, 128)]) == (10 * 5_202_227_200,
                                                     10 * 13_107_200)
    # past the window: keys 954..3049 are read, each query sees 2047
    fl, by = REF.paged_prefill_call(PHI, [(3000, 50)])
    assert fl == 20480 * 2047 * 50 == 2_096_128_000
    assert by == 2 * (2 * 10 * 128 * 2096 + 2 * 50 * 40 * 128) == 11_755_520


def test_prefill_rows_and_roofline():
    assert counts.prefill_rows(300, 0, 128) == [(0, 128), (128, 128),
                                                (256, 44)]
    assert counts.prefill_rows(300, 288, 128) == [(288, 12)]
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert counts.roofline_seconds(1.0, 819e9, pk) == pytest.approx(1.0)

"""Operation and byte counts against values worked out by hand, for one
decode and one prefill shape of Qwen2.5-3B and of a windowed GQA shape
(Phi-3-medium's widths, a 2047-key window)."""
import pytest

from harness import counts

QWEN = counts.Dims(D=2048, H=16, K=2, hd=128, F=11008, V=151936, L=36,
                   r=16)
PHI = counts.Dims(D=5120, H=40, K=10, hd=128, F=17920, V=32064, L=10, r=16,
                  window=2047)


def test_per_token_flops_qwen():
    # 2 * 36 * (q 2048*2048 + k,v 2*2048*256 + o 2048*2048 + mlp 3*2048*11008)
    assert counts.dense_flops(QWEN) == 5_549_064_192
    # 2 * 36 * 16 * (q 4096 + k,v 2*2304 + o 4096)
    assert counts.lora_flops(QWEN) == 14_745_600
    assert counts.head_flops(QWEN) == 622_329_856        # 2 * 2048 * 151936
    assert counts.attn_flops(QWEN, 99) == 4 * 36 * 16 * 128 * 100


def test_per_token_flops_phi():
    # 2 * 10 * (5120*5120 + 2*5120*1280 + 5120*5120 + 3*5120*17920)
    assert counts.dense_flops(PHI) == 6_815_744_000
    assert counts.lora_flops(PHI) == 10_649_600      # 320 * 33280
    assert counts.head_flops(PHI) == 328_335_360     # 2 * 5120 * 32064
    # the window caps the context at 2047 keys
    assert counts.attn_flops(PHI, 5000) == 4 * 10 * 40 * 128 * 2047


def test_decode_call_qwen():
    # rows at 100 (4 tokens kept) and 2000 (1 kept): contexts 101..104, 2001
    per_tok = 5_549_064_192 + 14_745_600 + 622_329_856
    want = 5 * per_tok + 294_912 * (101 + 102 + 103 + 104 + 2001)
    assert counts.decode_model_flops(QWEN, [100, 2000], [4, 1]) == want
    fl, by = counts.paged_decode_call(QWEN, [100, 2000])
    assert fl == 4 * 16 * 128 * (101 + 2001) == 17_219_584
    # K and V of 2102 positions over 2 heads, q and o of 16 heads, bf16
    assert by == 2 * (2 * 2 * 128 * 2102 + 2 * 2 * 16 * 128) == 2_168_832


def test_sgmv_call_qwen():
    fl, by = counts.sgmv_call(QWEN, rows=32, adapters=20, din=2048,
                              dout=2048)
    assert fl == 2 * 32 * 16 * 4096 == 4_194_304
    assert by == 2 * (32 * 4096 + 20 * 16 * 4096) == 2_883_584


def test_prefill_phi():
    # positions 256..299 computed (256 covered by the prefix cache)
    want = 44 * (6_815_744_000 + 10_649_600) \
        + 4 * 10 * 40 * 128 * sum(range(257, 301)) + 328_335_360
    assert counts.prefill_model_flops(PHI, 300, 256) == want
    assert sum(range(257, 301)) == 12_254
    # a chunk at 1920..2047: contexts 1921..2046, then 2047 twice
    fl, by = counts.paged_prefill_call(PHI, [(1920, 128)])
    assert fl == 20480 * 254_015 == 5_202_227_200
    assert by == 2 * (2 * 10 * 128 * 2048 + 2 * 128 * 40 * 128) == 13_107_200
    # past the window: keys 954..3049 are read, each query sees 2047
    fl, by = counts.paged_prefill_call(PHI, [(3000, 50)])
    assert fl == 20480 * 2047 * 50 == 2_096_128_000
    assert by == 2 * (2 * 10 * 128 * 2096 + 2 * 50 * 40 * 128) == 11_755_520


def test_prefill_rows_and_roofline():
    assert counts.prefill_rows(300, 0, 128) == [(0, 128), (128, 128),
                                                (256, 44)]
    assert counts.prefill_rows(300, 288, 128) == [(288, 12)]
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert counts.roofline_seconds(1.0, 819e9, pk) == pytest.approx(1.0)

"""The benchmark's FLOP and byte counts against values worked out by hand
for its configurations: GPT-3 Medium MoE at 4 layers and at all 12 (the
expert-parallel cell's depth), and DeepSeek-V2-Lite at 5 layers."""

import dataclasses

import pytest

import tiny_cells
from bench import counts, harness
from bench.reference import moe_lm


@pytest.fixture(scope="module")
def gpt3():
    return moe_lm.spec(harness.cell(tiny_cells.TRAIN).config)


@pytest.fixture(scope="module")
def dsv2():
    return moe_lm.spec(harness.cell(tiny_cells.SERVE).config)


def test_gpt3_l4_train(gpt3):
    # per layer: attention 4 x 1024^2 = 4,194,304; dense FFN 2 x 1024 x 4096
    # = 8,388,608; MoE: router 1024 x 64 + 2 experts x 2 x 1024 x 2048
    # = 8,454,144; tied head 1024 x 50304 = 51,511,296.  Layers 1 and 3 are
    # MoE.  4 x 4,194,304 + 2 x 8,388,608 + 2 x 8,454,144 + 51,511,296
    assert counts.matmul_params_per_token(gpt3) == 101_974_016
    # scores + values: 2 x 16 heads x 1024.5 positions x 2 x 64, 4 layers
    assert counts.attn_flops(gpt3, 1024.5) == 16_785_408
    # 3 x (2 x 101,974,016 + 16,785,408)
    assert counts.train_flops_per_token(gpt3, 2048) == 662_200_320
    assert counts.expert_flops(gpt3, 100) == 100 * 2 * 4_194_304


def test_gpt3_l12_train(gpt3):
    s = dataclasses.replace(gpt3, layers=12)
    # 12 x 4,194,304 + 6 x 8,388,608 + 6 x 8,454,144 + 51,511,296
    assert counts.matmul_params_per_token(s) == 202_899_456
    # 3 x (2 x 202,899,456 + 12 x 4096 x 1024.5)
    assert counts.train_flops_per_token(s, 2048) == 1_368_465_408


def test_dsv2_l5_serve(dsv2):
    # MLA: 2048x16x192 + 2048x512 + 2048x64 + 2 x 512x16x128 + 16x128x2048
    assert counts.attn_proj_params(dsv2) == 13_762_560
    # SwiGLU expert 3 x 2048 x 1408; MoE: router 2048 x 64 + 8 x expert
    assert counts.expert_params(dsv2) == 8_650_752
    assert counts.moe_active_params(dsv2) == 69_337_088
    # 5 x 13,762,560 + 67,239,936 + 4 x 69,337,088 + 2048 x 102400
    assert counts.matmul_params_per_token(dsv2) == 623_116_288
    # absorbed decode at 1000 positions: 5 layers x 2 x 16 x (576 + 512)
    assert counts.decode_flops(dsv2, 1000) == 2 * 623_116_288 + 174_080_000
    # 384-token prompt: 384 x 2 x 413,401,088 + 384 x 5 x 2 x 16 x 192.5 x
    # (192 + 128) + the head once, 2 x 2048 x 102400
    assert counts.prefill_flops(dsv2, 384) == 321_696_169_984


def test_dsv2_l5_bytes(dsv2):
    # every weight but the norms, bf16: 2,630,090,752 x 2
    assert counts.weight_bytes(dsv2) == 5_260_181_504
    assert counts.cache_row_bytes(dsv2) == 5 * 576 * 2
    # all 64 experts' weights + 64 rows in (bf16) and out (f32)
    assert counts.expert_call_bytes(dsv2, 64) == (
        64 * 8_650_752 * 2 + 64 * 2048 * 6)


@pytest.mark.parametrize("factor,plain", [(1, True), (40, False)])
def test_rope_scaling_read_as_plain_rope_only_at_factor_1(factor, plain):
    # the file keeps the published YaRN group at factor 1, which is plain
    # RoPE; the published factor 40 is not, and the reference refuses it
    cfg = dict(harness.cell(tiny_cells.SERVE).config)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], factor=factor)
    if plain:
        assert moe_lm.spec(cfg) == moe_lm.spec(dict(cfg, rope_scaling=None))
    else:
        with pytest.raises(NotImplementedError):
            moe_lm.spec(cfg)

"""Each configuration's FLOP function against a count made by hand."""

import json
import pathlib

import pytest

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_deepseek_v2_lite_2l():
    from flops import deepseek_v2

    # multiply-adds per token, forward
    wq = 2048 * 16 * (128 + 64)          # queries, nope + rope
    w_dkv = 2048 * (512 + 64)            # latent and shared rope key
    w_uk_uv = 512 * 16 * (128 + 128)     # keys and values from the latent
    wo = 16 * 128 * 2048
    attn_core = 16 * (192 + 128) * 2048 / 2   # causal scores + values
    dense_ffn = 3 * 2048 * 10944          # block 0
    moe = 2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2 * 1408   # block 1
    head = 2048 * 25600
    per_token = 2 * (wq + w_dkv + w_uk_uv + wo + attn_core) \
        + dense_ffn + moe + head
    want = 3 * 2 * per_token * 4 * 2048
    got = deepseek_v2.step_flops(load("deepseek-v2-lite-2l"), 4, 2048)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(1.1158e13, rel=1e-4)


def test_qwen1_5_moe_a2_7b_1l():
    from flops import qwen2_moe

    qkvo = 4 * 2048 * 2048                # 16 heads of 128, MHA
    attn_core = 16 * 2 * 128 * 2048 / 2
    moe = 2048 * 60 + 4 * 3 * 2048 * 1408 + 3 * 2048 * 5632
    head = 2048 * 18992
    want = 3 * 2 * (qkvo + attn_core + moe + head) * 4 * 2048
    got = qwen2_moe.step_flops(load("qwen1.5-moe-a2.7b-1l"), 4, 2048)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(6.35e12, rel=2e-3)

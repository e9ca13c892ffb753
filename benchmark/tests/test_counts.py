"""The count functions against hand-worked numbers for the two published
configurations."""
import json
import os

import pytest

from benchmark.counts import bert, gpt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_large_counts():
    cfg = _cfg("bert-large-pretrain")
    # BertForPreTraining(bert-large-uncased): 336,226,108 parameters
    assert bert.parameters(cfg) == 336_226_108
    # per layer 4*1024^2 + 2*1024*4096 = 12,582,912; x24
    assert bert.encoder_matmul_params(cfg) == 301_989_888
    B, s, P = cfg["train"]["batch"], 512, 80
    per_token = 6 * 301_989_888 + 12 * 24 * 512 * 1024
    per_masked = 6 * (1024 * 1024 + 1024 * 30522)
    assert bert.train_flops_per_step(cfg) == pytest.approx(
        B * (s * per_token + P * per_masked))
    # 8 x 512 tokens: 8.19e12; 16 x 512: 1.64e13
    assert bert.train_flops_per_step(cfg) / B == pytest.approx(1.0205e12, rel=1e-3)
    assert bert.train_min_bytes_per_step(cfg) == 28 * 336_226_108


def test_gpt2_xl_counts():
    cfg = _cfg("gpt2-xl-serve")
    assert gpt.parameters(cfg) == 1_557_611_200      # GPT-2 XL as published
    assert gpt.weight_bytes(cfg) == 3_115_222_400
    # K and V of one position: 2 x 48 layers x 1600 x 2 bytes
    assert gpt.kv_bytes_per_column(cfg) == 307_200
    # 32 rows with 300 valid columns each: 3.115 GB + 2.949 GB
    assert gpt.decode_step_min_bytes(cfg, 32 * 300) == 3_115_222_400 + 9600 * 307_200
    matmul = 48 * (4 * 1600 ** 2 + 2 * 1600 * 6400) + 50257 * 1600
    assert gpt.decode_step_flops(cfg, 32, 9600) == pytest.approx(
        2 * matmul * 32 + 4 * 1600 * 48 * 9600)

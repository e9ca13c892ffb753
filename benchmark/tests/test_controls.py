"""The controls, at a size a test run can hold: the reference computed in
the nearest precision below the configuration's, put in the program's
place, must come out as NOT correct against the limits of the config files.
(The readings at the cells' own sizes, on the chip, are in PERF.md
section 2; ``benchmark/control.py`` takes them.)"""
import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.reference import gpt as gpt_ref
from benchmark.reference.common import CONTROL_PRECISION
from benchmark.runners import train_step

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _file(rel):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def test_float8_training_control_is_not_correct():
    cell = bench_run.load_cell("bert-large-pretrain-s512")
    cfg = cell["config"]
    assert cfg["dtype"] == "bfloat16"          # so the control is float8
    cfg.update(vocab_size=2048, hidden_size=256, num_hidden_layers=8,
               num_attention_heads=4, intermediate_size=1024,
               max_position_embeddings=128)
    cfg["train"].update(batch=8, seq=128, masked_per_seq=20)
    cfg["reference"]["rows_per_block"] = 4
    lim = _file("configs/bert-large-pretrain.json")["limits"]
    for row in train_step.control(cell, [5, 2 ** 31 + 6]):
        assert row["precision"] == "float8_e4m3"
        # the number the control is there to fail (PERF.md section 2)
        assert row["grad_diff_rel"] > lim["grad_diff_rel"]


def test_float8_serving_control_is_not_correct():
    """Somewhere in a few hundred positions, the token that float8 puts
    first lies further below the float32 reference's best than the limit
    allows; the configuration's own bfloat16 stays far inside it."""
    cfg = _file("configs/gpt2-xl-serve.json")
    lim = cfg["limits"]["served_gap_rel"]
    cfg.update(vocab_size=1024, n_embd=128, n_layer=24, n_head=4, n_inner=512,
               n_positions=256, n_ctx=256)
    low = CONTROL_PRECISION[cfg["dtype"]]
    widest = 0.0
    for seed in (43, 44, 45, 2 ** 31 + 46):
        w = gpt_ref.init_weights(cfg, seed)
        ids = np.random.default_rng(seed).integers(0, 1024, 256, dtype=np.int32)
        widest = max(widest, float(np.max(
            gpt_ref.position_gaps(cfg, w, ids, low))))
        assert float(np.max(gpt_ref.position_gaps(
            cfg, w, ids, cfg["dtype"]))) < lim / 3
    assert widest > lim      # a widest gap: it swings from seed to seed

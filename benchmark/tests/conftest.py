"""CPU rehearsals of the benchmark at tiny sizes.  Run by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider

Not part of tier-1 (the driver runs ``tests/`` only)."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


@pytest.fixture
def bert_tiny():
    """The BERT configuration file cut to a size the CPU holds."""
    cfg = _load("configs/bert-large-pretrain.json")
    cfg.update(vocab_size=128, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=2, intermediate_size=128,
               max_position_embeddings=64, dtype="float32")
    cfg["train"].update(batch=4, seq=32, masked_per_seq=5)
    cfg["reference"]["rows_per_block"] = 2
    return cfg


@pytest.fixture
def gpt_tiny():
    """The GPT configuration file cut to a size the CPU holds."""
    cfg = _load("configs/gpt2-xl-serve.json")
    cfg.update(vocab_size=128, n_embd=32, n_layer=2, n_head=2, n_inner=128,
               n_positions=128, n_ctx=128, dtype="float32")
    cfg["serve"].update(slots=4, max_len=128, max_new_tokens=16,
                        prefill_chunk=16, seq_buckets=[16, 32, 64, 128],
                        batch_buckets=[1, 2], workers=4)
    return cfg


def _chat_tiny(name, **kw):
    tr = _load(f"traffic/{name}.json")
    tr.update(ramp_s=0.5, pool_requests=64, trace_slice_s=0.5,
              prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.8,
                          "min": 4, "max": 96},
              max_new_tokens={"dist": "lognormal", "median": 6, "sigma": 0.7,
                              "min": 2, "max": 16})
    tr.update(kw)
    return tr


@pytest.fixture
def closed_tiny():
    return _chat_tiny("chat-closed-2S", job_requests=8)


@pytest.fixture
def open_tiny():
    return _chat_tiny("chat-poisson", rate_per_s=20.0, drain_s=5.0)

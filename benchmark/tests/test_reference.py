"""The plain references against the program's own forward (and its
TrainStep) at tiny sizes in float32, on the benchmark's weights."""
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from benchmark.generators import train_stream
from benchmark.models import bert as bert_model
from benchmark.models import gpt as gpt_model
from benchmark.reference import bert as bert_ref
from benchmark.reference import gpt as gpt_ref
from benchmark.reference.common import CONTROL_PRECISION


def test_bert_loss_matches_program(bert_tiny):
    w = bert_ref.init_weights(bert_tiny, 2 ** 31 + 7)
    model = bert_model.build(bert_tiny, bert_model.to_program(w))
    model.eval()
    ids, pos, labels = train_stream.make({"pool_batches": 1}, bert_tiny, 3)[0]
    got = float(model(paddle.to_tensor(ids), None, None,
                      paddle.to_tensor(labels), None, paddle.to_tensor(pos)))
    want = float(bert_ref.mlm_loss(bert_tiny, w, jnp.asarray(ids),
                                   jnp.asarray(pos), jnp.asarray(labels)))
    assert abs(got - want) < 1e-5 * abs(want)


def test_bert_block_rows_add_up(bert_tiny):
    """Walking the batch in blocks of rows gives the whole batch's steps."""
    w = bert_ref.init_weights(bert_tiny, 5)
    pool = train_stream.make({"pool_batches": 2}, bert_tiny, 5)
    a = bert_ref.train_steps(bert_tiny, w, pool, rows_per_block=1)
    b = bert_ref.train_steps(bert_tiny, w, pool, rows_per_block=4)
    assert np.allclose(a[0], b[0], rtol=1e-5)
    assert np.allclose(a[1][0]["word"], b[1][0]["word"], rtol=1e-4)
    assert np.allclose(a[1][1]["layers/q_w"], b[1][1]["layers/q_w"],
                       rtol=1e-3, atol=1e-7)


def test_gpt_logits_match_program(gpt_tiny):
    w = gpt_ref.init_weights(gpt_tiny, 11, dtype=jnp.float32)
    model = gpt_model.build(gpt_tiny, gpt_model.to_program(w))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, 21, dtype=np.int32)
    served = rng.integers(0, 128, 9, dtype=np.int32)
    full = np.concatenate([prompt, served[:-1]])
    out = model(paddle.to_tensor(full[None].astype(np.int64)))
    got = np.asarray(out.numpy(), np.float32)[0, prompt.size - 1:]
    want = np.asarray(gpt_ref.served_logits(gpt_tiny, w, prompt, served))
    assert got.shape == want.shape == (9, 128)
    assert np.abs(got - want).max() < 1e-4


def test_gpt_control_differs_from_reference(gpt_tiny):
    """The lower precision moves the logits; float32 against itself does not."""
    w = gpt_ref.init_weights(gpt_tiny, 11, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, 40, dtype=np.int32)
    served = rng.integers(0, 128, 16, dtype=np.int32)
    ref = np.asarray(gpt_ref.served_logits(gpt_tiny, w, prompt, served))
    low = np.asarray(gpt_ref.served_logits(
        gpt_tiny, w, prompt, served, CONTROL_PRECISION["bfloat16"]))
    assert np.abs(ref - low).max() > 1e-3
    same = np.asarray(gpt_ref.control_gaps(gpt_tiny, w, prompt, served, "float32"))
    assert same.max() == 0.0

"""nn layer tests vs torch-CPU references where useful (SURVEY.md §4.2)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def r(*shape):
    return np.random.RandomState(int(np.prod(shape)) % 97).randn(
        *shape).astype(np.float32)


class TestLinearConv:
    def test_linear_matches_numpy(self):
        lin = nn.Linear(4, 3)
        x = r(2, 4)
        out = lin(paddle.to_tensor(x))
        expect = x @ lin.weight.numpy() + lin.bias.numpy()
        np.testing.assert_allclose(out.numpy(), expect, rtol=1e-5)

    def test_conv2d_matches_torch(self):
        torch = pytest.importorskip("torch")
        x = r(2, 3, 8, 8)
        conv = nn.Conv2D(3, 6, 3, stride=2, padding=1)
        out = conv(paddle.to_tensor(x))
        tout = torch.nn.functional.conv2d(
            torch.tensor(x), torch.tensor(conv.weight.numpy()),
            torch.tensor(conv.bias.numpy()), stride=2, padding=1)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_conv2d_groups_dilation(self):
        torch = pytest.importorskip("torch")
        x = r(1, 4, 9, 9)
        conv = nn.Conv2D(4, 8, 3, groups=2, dilation=2)
        out = conv(paddle.to_tensor(x))
        tout = torch.nn.functional.conv2d(
            torch.tensor(x), torch.tensor(conv.weight.numpy()),
            torch.tensor(conv.bias.numpy()), dilation=2, groups=2)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_conv2d_transpose_matches_torch(self):
        torch = pytest.importorskip("torch")
        x = r(1, 3, 5, 5)
        conv = nn.Conv2DTranspose(3, 4, 3, stride=2, padding=1,
                                  output_padding=1)
        out = conv(paddle.to_tensor(x))
        tout = torch.nn.functional.conv_transpose2d(
            torch.tensor(x), torch.tensor(conv.weight.numpy()),
            torch.tensor(conv.bias.numpy()), stride=2, padding=1,
            output_padding=1)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-4,
                                   atol=1e-5)


class TestPoolNorm:
    def test_maxpool_avgpool_match_torch(self):
        torch = pytest.importorskip("torch")
        x = r(2, 3, 8, 8)
        out = F.max_pool2d(paddle.to_tensor(x), 2, 2)
        tout = torch.nn.functional.max_pool2d(torch.tensor(x), 2, 2)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-6)
        out = F.avg_pool2d(paddle.to_tensor(x), 3, 2, 1)
        tout = torch.nn.functional.avg_pool2d(torch.tensor(x), 3, 2, 1,
                                              count_include_pad=False)
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-5)

    def test_batchnorm_train_eval(self):
        bn = nn.BatchNorm2D(3)
        x = r(4, 3, 5, 5) * 3 + 1
        bn.train()
        out = bn(paddle.to_tensor(x))
        # normalized output: near zero mean/unit var per channel
        m = out.numpy().mean(axis=(0, 2, 3))
        v = out.numpy().var(axis=(0, 2, 3))
        np.testing.assert_allclose(m, np.zeros(3), atol=1e-5)
        np.testing.assert_allclose(v, np.ones(3), rtol=1e-3)
        # running stats moved toward batch stats
        assert np.abs(bn._mean.numpy()).sum() > 0
        bn.eval()
        out2 = bn(paddle.to_tensor(x))
        assert out2.shape == [4, 3, 5, 5]

    def test_layernorm_matches_torch(self):
        torch = pytest.importorskip("torch")
        ln = nn.LayerNorm(16)
        x = r(2, 5, 16)
        out = ln(paddle.to_tensor(x))
        tout = torch.nn.functional.layer_norm(
            torch.tensor(x), (16,), torch.tensor(ln.weight.numpy()),
            torch.tensor(ln.bias.numpy()))
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_groupnorm(self):
        gn = nn.GroupNorm(2, 4)
        x = r(2, 4, 3, 3)
        out = gn(paddle.to_tensor(x))
        assert out.shape == [2, 4, 3, 3]


class TestEmbeddingDropout:
    def test_embedding_lookup_and_grad(self):
        emb = nn.Embedding(10, 4)
        ids = paddle.to_tensor(np.array([[1, 2], [3, 1]]))
        out = emb(ids)
        np.testing.assert_allclose(out.numpy()[0, 0], emb.weight.numpy()[1])
        loss = out.sum()
        loss.backward()
        g = emb.weight.grad.numpy()
        # row 1 used twice
        np.testing.assert_allclose(g[1], 2 * np.ones(4))
        np.testing.assert_allclose(g[5], np.zeros(4))

    def test_dropout_train_eval(self):
        paddle.seed(7)
        x = paddle.ones([1000])
        out = F.dropout(x, 0.5, training=True)
        frac_zero = float((out.numpy() == 0).mean())
        assert 0.3 < frac_zero < 0.7
        # upscale preserves expectation
        assert abs(out.numpy().mean() - 1.0) < 0.2
        out_eval = F.dropout(x, 0.5, training=False)
        np.testing.assert_allclose(out_eval.numpy(), x.numpy())


class TestActivationsLosses:
    def test_softmax_ce_matches_torch(self):
        torch = pytest.importorskip("torch")
        logits = r(8, 5)
        labels = np.random.RandomState(3).randint(0, 5, (8,))
        loss = F.cross_entropy(paddle.to_tensor(logits),
                               paddle.to_tensor(labels))
        tl = torch.nn.functional.cross_entropy(torch.tensor(logits),
                                               torch.tensor(labels))
        np.testing.assert_allclose(loss.numpy(), tl.numpy(), rtol=1e-5)

    def test_ce_ignore_index(self):
        logits = r(4, 3)
        labels = np.array([0, 1, -100, 2])
        loss = F.cross_entropy(paddle.to_tensor(logits),
                               paddle.to_tensor(labels), ignore_index=-100)
        keep = labels != -100
        lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        expect = -lp[keep, labels[keep]].mean()
        np.testing.assert_allclose(loss.numpy(), expect, rtol=1e-4)

    def test_gelu_matches_torch(self):
        torch = pytest.importorskip("torch")
        x = r(5, 5)
        out = F.gelu(paddle.to_tensor(x))
        tout = torch.nn.functional.gelu(torch.tensor(x))
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_bce_logits(self):
        torch = pytest.importorskip("torch")
        x, y = r(6), (np.random.RandomState(5).rand(6) > 0.5).astype(np.float32)
        out = F.binary_cross_entropy_with_logits(paddle.to_tensor(x),
                                                 paddle.to_tensor(y))
        tout = torch.nn.functional.binary_cross_entropy_with_logits(
            torch.tensor(x), torch.tensor(y))
        np.testing.assert_allclose(out.numpy(), tout.numpy(), rtol=1e-5)


class TestRNNTransformer:
    def test_lstm_matches_torch(self):
        torch = pytest.importorskip("torch")
        lstm = nn.LSTM(4, 8)
        tl = torch.nn.LSTM(4, 8, batch_first=True)
        tl.weight_ih_l0.data = torch.tensor(lstm.weight_ih_l0.numpy())
        tl.weight_hh_l0.data = torch.tensor(lstm.weight_hh_l0.numpy())
        tl.bias_ih_l0.data = torch.tensor(lstm.bias_ih_l0.numpy())
        tl.bias_hh_l0.data = torch.tensor(lstm.bias_hh_l0.numpy())
        x = r(2, 5, 4)
        out, (h, c) = lstm(paddle.to_tensor(x))
        tout, (th, tc) = tl(torch.tensor(x))
        np.testing.assert_allclose(out.numpy(), tout.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(h.numpy(), th.detach().numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_mha_self_attention_shape_and_grad(self):
        mha = nn.MultiHeadAttention(16, 4)
        x = paddle.to_tensor(r(2, 6, 16))
        out = mha(x)
        assert out.shape == [2, 6, 16]
        out.sum().backward()
        assert mha.q_proj.weight.grad is not None

    def test_transformer_full(self):
        model = nn.Transformer(d_model=16, nhead=2, num_encoder_layers=2,
                               num_decoder_layers=2, dim_feedforward=32)
        src = paddle.to_tensor(r(2, 5, 16))
        tgt = paddle.to_tensor(r(2, 4, 16))
        out = model(src, tgt)
        assert out.shape == [2, 4, 16]

    def test_encoder_cache_decode(self):
        layer = nn.TransformerEncoderLayer(8, 2, 16)
        enc = nn.TransformerEncoder(layer, 2)
        x = paddle.to_tensor(r(1, 3, 8))
        out = enc(x)
        assert out.shape == [1, 3, 8]

"""Fused conv+BN(+ReLU) Pallas pipeline — interpret-mode value/grad checks
vs the XLA (lax.conv + batch-norm) reference path, the space-to-depth stem
equivalence, and the honesty gate (ISSUE 2 tentpole; VERDICT r5 #1).

Everything here runs under tier-1's ``JAX_PLATFORMS=cpu`` via the kernels'
interpret mode; the on-chip end-to-end decision lives in PERF.md round-6.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.fused_conv import (
    enabled, fused_conv_bn_act, stem_s2d_input, stem_s2d_weight,
    stem_supported, supports)


def _ref(x, w, g, b, stride, pad, eps=1e-5, relu=True):
    """lax conv + train-mode BN + relu — what XLA runs on the off path."""
    wk = jnp.transpose(w, (2, 3, 1, 0))
    dn = jax.lax.conv_dimension_numbers(x.shape, wk.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    y = jax.lax.conv_general_dilated(
        x, wk, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=dn).astype(x.dtype)
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=(0, 1, 2))
    var = jnp.var(yf, axis=(0, 1, 2))
    out = (yf - mean) * jax.lax.rsqrt(var + eps) * g + b
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype), mean, var


def _inputs(n=2, h=8, cin=4, cout=8, k=3, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, h, h, cin), jnp.float32)
    w = jnp.asarray(rng.randn(cout, cin, k, k) * 0.1, jnp.float32)
    g = jnp.asarray(rng.rand(cout) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(cout) * 0.1, jnp.float32)
    return x, w, g, b


@pytest.mark.parametrize("k,stride,pad,relu", [
    (3, 1, 1, True),      # the 3×3/s1 bulk of stages 1–2
    (1, 1, 0, False),     # bottleneck 1×1 (BN-only epilogue: pre-add)
    (3, 2, 1, True),      # downsample 3×3/s2
    (1, 2, 0, True),      # downsample 1×1/s2 shortcut
    (5, 1, 2, True),      # widest supported tap
])
def test_forward_matches_xla(k, stride, pad, relu):
    x, w, g, b = _inputs(k=k)
    y, m, v = fused_conv_bn_act(x, w, g, b, stride, pad, 1e-5, relu)
    yr, mr, vr = _ref(x, w, g, b, stride, pad, relu=relu)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("k,stride,pad,relu", [
    (3, 1, 1, True), (1, 1, 0, False), (3, 2, 1, True),
])
def test_vjp_matches_xla(k, stride, pad, relu):
    """dX/dW/dγ/dβ of the custom VJP vs jax.grad through the jnp path."""
    x, w, g, b = _inputs(k=k, seed=2)
    rng = np.random.RandomState(3)
    y0, _, _ = fused_conv_bn_act(x, w, g, b, stride, pad, 1e-5, relu)
    cot = jnp.asarray(rng.randn(*y0.shape), jnp.float32)

    def loss_pallas(x, w, g, b):
        y, _, _ = fused_conv_bn_act(x, w, g, b, stride, pad, 1e-5, relu)
        return jnp.sum(y * cot)

    def loss_ref(x, w, g, b):
        y, _, _ = _ref(x, w, g, b, stride, pad, relu=relu)
        return jnp.sum(y * cot)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(x, w, g, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, g, b)
    for a, r, name in zip(gp, gr, ("dx", "dw", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-3,
                                   atol=2e-3, err_msg=name)


def test_stats_cotangents_flow():
    """Gradients THROUGH the returned mean/var (a stat-regularizing loss)
    match the jnp path — the running-update chain stays differentiable."""
    x, w, g, b = _inputs(seed=4)

    def loss_pallas(x):
        _, m, v = fused_conv_bn_act(x, w, g, b, 1, 1, 1e-5, False)
        return jnp.sum(m * m) + jnp.sum(v)

    def loss_ref(x):
        _, m, v = _ref(x, w, g, b, 1, 1, relu=False)
        return jnp.sum(m * m) + jnp.sum(v)

    np.testing.assert_allclose(np.asarray(jax.grad(loss_pallas)(x)),
                               np.asarray(jax.grad(loss_ref)(x)),
                               rtol=1e-4, atol=1e-5)


def test_bf16_activation_path():
    x, w, g, b = _inputs(seed=5)
    y, _, _ = fused_conv_bn_act(x.astype(jnp.bfloat16),
                                w.astype(jnp.bfloat16), g, b, 1, 1, 1e-5,
                                True)
    assert y.dtype == jnp.bfloat16
    yr, _, _ = _ref(x, w, g, b, 1, 1, relu=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=5e-2,
                               atol=5e-2)


def test_stem_s2d_equivalence():
    """pad3 + s2d(2) + 4×4/s1 VALID ≡ 7×7/s2/p3 — the weight/input reorg
    is exact, not approximate."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32)
    w7 = jnp.asarray(rng.randn(8, 3, 7, 7) * 0.1, jnp.float32)
    wk = jnp.transpose(w7, (2, 3, 1, 0))
    dn = jax.lax.conv_dimension_numbers(x.shape, wk.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    yref = jax.lax.conv_general_dilated(x, wk, (2, 2), [(3, 3), (3, 3)],
                                        dimension_numbers=dn)
    x2, w2 = stem_s2d_input(x), stem_s2d_weight(w7)
    assert x2.shape == (2, 11, 11, 12) and w2.shape == (8, 12, 4, 4)
    wk2 = jnp.transpose(w2, (2, 3, 1, 0))
    dn2 = jax.lax.conv_dimension_numbers(x2.shape, wk2.shape,
                                         ("NHWC", "HWIO", "NHWC"))
    y2 = jax.lax.conv_general_dilated(x2, wk2, (1, 1), "VALID",
                                      dimension_numbers=dn2)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(yref), rtol=1e-4,
                               atol=1e-4)


def test_supports_is_selective():
    # the real stage-1/2 shapes qualify
    assert supports((256, 56, 56, 64), (64, 64, 1, 1), 1, 0)
    assert supports((256, 56, 56, 64), (256, 64, 3, 3), 1, 1)
    assert supports((256, 28, 28, 128), (128, 128, 3, 3), 1, 1)
    # NCHW, groups, dilation, 7×7 direct, stride 3 all decline
    assert not supports((2, 8, 8, 4), (8, 4, 3, 3), 1, 1,
                        channel_last=False)
    assert not supports((2, 8, 8, 4), (8, 2, 3, 3), 1, 1, groups=2)
    assert not supports((2, 8, 8, 4), (8, 4, 3, 3), 1, 1, dilation=2)
    assert not supports((2, 224, 224, 3), (64, 3, 7, 7), 2, 3)
    assert not supports((2, 8, 8, 4), (8, 4, 3, 3), 3, 1)
    # untileable M declines (the pad-to-8 rule)
    assert not supports((1, 5, 5, 4), (8, 4, 3, 3), 2, 1)
    # the s2d stem: its 16 taps at a 112x112 output need more VMEM stack
    # than the chip's compiler allows (tests/test_tpu_compile.py); a
    # 160px input fits
    assert not stem_supported((256, 224, 224, 3), (64, 3, 7, 7), itemsize=2)
    assert stem_supported((256, 160, 160, 3), (64, 3, 7, 7), itemsize=2)
    assert not stem_supported((256, 225, 225, 3), (64, 3, 7, 7))
    assert not stem_supported((256, 224, 224, 3), (64, 3, 3, 3))


def test_gate_defaults_off(monkeypatch):
    """Honesty rule: no end-to-end win is recorded on the bench chip yet,
    so the fused path must be opt-in (ops/pallas/fused_bn.py precedent)."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_CONV", raising=False)
    assert enabled() is False
    monkeypatch.setenv("PADDLE_TPU_PALLAS_CONV", "1")
    assert enabled() is True


def test_flag_registry_gate():
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_use_pallas_fused_conv": True})
    try:
        assert enabled() is True
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": False})


def test_off_path_is_one_branch_and_falls_back_cleanly():
    """With the gate off, Conv2D+BN+ReLU must not touch the fused op at
    all; with the gate on but an ineligible site (NCHW), the layer chain
    must fall back to the XLA path with identical results."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from unittest import mock

    rng = np.random.RandomState(7)
    x = rng.randn(2, 6, 6, 4).astype("float32")

    def run():
        paddle.seed(0)
        net = nn.Sequential(
            nn.Conv2D(4, 8, 3, padding=1, bias_attr=False,
                      data_format="NHWC"),
            nn.BatchNorm2D(8, data_format="NHWC"), nn.ReLU())
        net.train()
        return np.asarray(net(paddle.to_tensor(x)).numpy())

    paddle.set_flags({"FLAGS_use_pallas_fused_conv": False})
    with mock.patch("paddle_tpu.ops.pallas.fused_conv.fused_conv_bn_act",
                    side_effect=AssertionError("fused op on the off path")):
        off = run()

    # gate on, NCHW model: fusable() declines, XLA path runs, same math
    paddle.set_flags({"FLAGS_use_pallas_fused_conv": True})
    try:
        paddle.seed(0)
        net = nn.Sequential(
            nn.Conv2D(4, 8, 3, padding=1, bias_attr=False,
                      data_format="NCHW"),
            nn.BatchNorm2D(8, data_format="NCHW"), nn.ReLU())
        net.train()
        xc = np.transpose(x, (0, 3, 1, 2))
        nchw = np.asarray(net(paddle.to_tensor(xc)).numpy())
        np.testing.assert_allclose(np.transpose(nchw, (0, 2, 3, 1)), off,
                                   rtol=1e-4, atol=1e-5)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": False})


def test_layer_dispatch_matches_xla_end_to_end():
    """Gate on vs off through the real Layer chain (Conv2D → BatchNorm2D →
    ReLU): identical outputs, gradients, and running stats."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    rng = np.random.RandomState(8)
    xnp = rng.randn(4, 8, 8, 4).astype("float32")

    def run(gate):
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": gate})
        paddle.seed(0)
        net = nn.Sequential(
            nn.Conv2D(4, 8, 3, padding=1, bias_attr=False,
                      data_format="NHWC"),
            nn.BatchNorm2D(8, data_format="NHWC"),
            nn.ReLU(),
            nn.Conv2D(8, 8, 1, stride=2, bias_attr=False,
                      data_format="NHWC"),
            nn.BatchNorm2D(8, data_format="NHWC"))
        net.train()
        out = net(paddle.to_tensor(xnp))
        loss = paddle.mean(out ** 2)
        loss.backward()
        grads = {n: np.asarray(p.grad.numpy())
                 for n, p in net.named_parameters() if p.grad is not None}
        stats = {}
        for name, sub in net.named_sublayers():
            for bn, bv in getattr(sub, "_buffers", {}).items():
                stats[f"{name}.{bn}"] = np.asarray(bv.numpy())
        return np.asarray(out.numpy()), grads, stats

    try:
        o0, g0, s0 = run(False)
        o1, g1, s1 = run(True)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": False})
    np.testing.assert_allclose(o0, o1, rtol=1e-4, atol=1e-5)
    assert set(g0) == set(g1)
    for n in g0:
        np.testing.assert_allclose(g0[n], g1[n], rtol=1e-3, atol=1e-4,
                                   err_msg=n)
    for n in s0:
        np.testing.assert_allclose(s0[n], s1[n], rtol=1e-4, atol=1e-5,
                                   err_msg=n)


def test_resnet_stem_s2d_trainstep():
    """ResNet NHWC TrainStep with the gate on (s2d stem + fused blocks)
    tracks the XLA trajectory."""
    import paddle_tpu as paddle
    import jax.numpy as jnp
    from paddle_tpu.parallel import init_mesh, TrainStep
    from paddle_tpu.vision.models import resnet18

    rng = np.random.RandomState(9)
    xnp = rng.randn(2, 32, 32, 3).astype("float32")
    ynp = rng.randint(0, 10, (2,))

    def run(gate):
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": gate})
        paddle.seed(1)
        model = resnet18(data_format="NHWC", num_classes=10)
        mesh = init_mesh({"dp": -1})
        opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                        learning_rate=0.01, momentum=0.9)
        step = TrainStep(model, opt, loss_fn=paddle.nn.CrossEntropyLoss(),
                         mesh=mesh)
        return [float(step((jnp.asarray(xnp),), jnp.asarray(ynp)))
                for _ in range(3)]

    try:
        base = run(False)
        fused = run(True)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fused_conv": False})
    assert all(np.isfinite(fused))
    # the first forward/loss must agree tightly (same math); later steps
    # are chaotic at batch 2 (a 1e-3 logit drift compounds through the
    # momentum update), so the gate there is descent, not equality
    np.testing.assert_allclose(base[0], fused[0], rtol=1e-3, atol=1e-3)
    assert fused[-1] < fused[0]

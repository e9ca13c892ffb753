"""Sandbox compiles for the chip: every Pallas kernel family of
chip_smoke.py's kernels phase, at that phase's shapes, handed to the TPU
compiler for a *described* (not attached) ``v5e:2x2``.

Interpret mode passes shapes the chip's compiler refuses (block shapes
that do not tile, a VMEM stack over the limit), so
these compiles are the regression guard that costs no chip time.  Nothing
runs: a passing compile is not a chip run.

One file on purpose: only one process at a time may load libtpu, the
xdist worker that gets this file keeps it until it exits.  The topology is
described inside the fixture, never at import or collection.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from paddle_tpu.ops.pallas import (flash_attention_fn, fused_bn,
                                   fused_conv, packed_attention_fn, supports,
                                   supports_packed)
from paddle_tpu.ops.pallas import _mode

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def cache_off():
    """JAX's compilation cache off around these compiles: an entry written
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def compile_for_chip(one_chip, cache_off, monkeypatch):
    """compile(fn, *(shape, dtype)) -> optimized HLO text, with the kernels
    steered out of interpret mode for the compile."""
    def compile_(fn, *specs):
        avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in specs]
        with monkeypatch.context() as m:
            m.setattr(_mode, "interpret", lambda: False)
            return jax.jit(fn).lower(*avals).compile().as_text()
    return compile_


B, N, S, H = chip_smoke.FULL.attn
QKV = ((B, N, S, H), BF16)
CONV_X, CONV_W = chip_smoke.FULL.conv_x, chip_smoke.FULL.conv_w
COUT = CONV_W[0]
CH = ((COUT,), jnp.float32)
X2D = ((CONV_X[0] * CONV_X[1] * CONV_X[2], CONV_X[3]), BF16)


def _flash(q, k, v):
    return flash_attention_fn(q, k, v, causal=True)


BT, NT, ST, HT = chip_smoke.FULL.attn_train
QKV_T = ((BT, ST, NT * HT), BF16)


def _packed(q, k, v):
    return packed_attention_fn(q, k, v, NT)


def _conv(x, w, g, b):
    return fused_conv.fused_conv_bn_act(x, w, g, b, 1, 1, 1e-5, True)


def _bn(x, g, b):
    return fused_bn.fused_bn_act(x, g, b, 1e-5, True)


def _loss(fn):
    def loss(*a):
        out = fn(*a)
        out = out[0] if isinstance(out, tuple) else out
        return out.astype(jnp.float32).sum()
    return loss


def _latent(H, d_n, d_v, r_kv, K, S, masked):
    """The latent family's chunk kernel at a published width set: 512
    queries over column blocks of 512 of a ``[1, S, K]`` plane, the span
    traced; ``masked``: a membership / ring mask handed in."""
    from paddle_tpu.ops.pallas.latent_attention import \
        latent_chunk_attention_fn
    i32 = ((), jnp.int32)

    def fn(q, w_uk, w_uv, plane, start, pos, lo, hi, *keep):
        return latent_chunk_attention_fn(
            q, w_uk, w_uv, plane, start, pos, lo, hi, r_kv=r_kv,
            scale=0.07, block=512, keep=keep[0] if keep else None)
    return fn, (((1, H, 512, d_n + 64), BF16), ((H, r_kv, d_n), BF16), ((H, r_kv, d_v), BF16),
                ((1, S, K), BF16), ((1,), jnp.int32), i32, i32, i32) \
        + ((((1, 512, S), jnp.bool_),) if masked else ())


def _decode_rows(rows, heads, hd, rep, groups, cols, dtype=BF16):
    """The decode step's per-row read (ops/pallas/span_decode.py) at a
    served geometry: one query a row over ``[rows, groups, cols, 128]``
    planes in blocks of 128, each row's window traced."""
    from paddle_tpu.nn.functional.attention import _decode_rows_fn
    lanes = max(128, hd)
    plane = ((rows, groups, cols, lanes), dtype)
    return (lambda q, k, v, start, end: _decode_rows_fn(
        q, k, v, start, end, block=128, rep=rep),
        (((rows, heads, 1, hd), dtype), plane, plane,
         ((rows,), jnp.int32), ((rows,), jnp.int32)))


CASES = {
    "decode_rows_gpt2_xl": _decode_rows(32, 25, 64, 1, 13, 1024),
    "decode_rows_lfm2": _decode_rows(128, 32, 64, 4, 4, 8192),
    "decode_rows_nemotron3": _decode_rows(48, 32, 128, 16, 2, 8192),
    # a plane the block does not divide (the last block starts early), f32
    "decode_rows_f32_ragged": _decode_rows(8, 25, 64, 1, 13, 1024 + 64,
                                           jnp.float32),
    "latent_chunk_dots3_full": _latent(128, 128, 128, 512, 640, 12288, True),
    "latent_chunk_kimi": _latent(64, 128, 128, 512, 640, 16384, False),
    "latent_chunk_glm5": _latent(64, 192, 256, 512, 640, 24576, True),
    "latent_chunk_dots3_window": _latent(64, 192, 128, 1024, 1152, 1024,
                                         True),
    "flash_attention_fwd": (_flash, (QKV, QKV, QKV)),
    "flash_attention_bwd": (jax.grad(_loss(_flash), argnums=(0, 1, 2)),
                            (QKV, QKV, QKV)),
    "single_block_attention_fwd": (_packed, (QKV_T, QKV_T, QKV_T)),
    "single_block_attention_bwd": (jax.grad(_loss(_packed),
                                            argnums=(0, 1, 2)),
                                   (QKV_T, QKV_T, QKV_T)),
    "fused_conv_bn_relu_fwd": (_conv, ((CONV_X, BF16), (CONV_W, BF16),
                                       CH, CH)),
    "fused_conv_bn_relu_bwd": (
        jax.grad(_loss(_conv), argnums=(0, 1, 2, 3)),
        ((CONV_X, BF16), (CONV_W, BF16), CH, CH)),
    "fused_bn_relu_fwd": (_bn, (X2D, ((CONV_X[3],), jnp.float32),
                                ((CONV_X[3],), jnp.float32))),
    "fused_bn_relu_bwd": (
        jax.grad(_loss(_bn), argnums=(0, 1, 2)),
        (X2D, ((CONV_X[3],), jnp.float32), ((CONV_X[3],), jnp.float32))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, compile_for_chip):
    fn, specs = CASES[name]
    assert "tpu_custom_call" in compile_for_chip(fn, *specs), \
        f"{name}: no Mosaic custom call in the compiled program"


def test_gates_admit_the_compiled_shapes():
    """What the kernels phase compiles is what the dispatch gates admit
    (head dim 64 included) — a gate that said no would hide the kernel."""
    assert supports((B, N, S, H), (B, N, S, H), causal=True)
    assert supports_packed((BT, NT, ST, HT), (BT, NT, ST, HT))
    assert fused_conv.supports(CONV_X, CONV_W, stride=1, padding=1,
                               itemsize=2)
    assert fused_bn._pick_tile(*X2D[0]) > 0


# (x NHWC, w OIHW, stride, padding): sites fused_conv.supports admits
# close to its caps, and sites the chip's compiler refused ("ran out of
# memory in memory space vmem") while the pre-PR-21 estimate admitted them
ADMITTED_NEAR_CAP = [
    ((8, 112, 112, 64), (64, 64, 3, 3), 1, 1),
    ((8, 80, 80, 256), (64, 256, 3, 3), 1, 1),
    ((8, 83, 83, 12), (64, 12, 4, 4), 1, 0),        # the s2d stem, 160px
    ((8, 28, 28, 256), (256, 256, 3, 3), 2, 1),
]
REFUSED_BY_COMPILER = [
    ((8, 96, 96, 256), (32, 256, 3, 3), 1, 1),
    ((8, 115, 115, 12), (64, 12, 4, 4), 1, 0),      # the s2d stem, 224px
    ((8, 56, 56, 128), (128, 128, 3, 3), 2, 1),     # ResNet-50 stage 2
    ((8, 80, 80, 128), (512, 128, 3, 3), 2, 1),
    ((8, 28, 28, 16), (32, 16, 5, 5), 2, 2),
]


@pytest.mark.parametrize("x,w,stride,padding", ADMITTED_NEAR_CAP)
def test_fused_conv_gate_admits_only_what_compiles(x, w, stride, padding,
                                                   compile_for_chip):
    assert fused_conv.supports(x, w, stride=stride, padding=padding,
                               itemsize=2)
    cout = w[0]
    text = compile_for_chip(
        lambda a, b, g, s: fused_conv.fused_conv_bn_act(
            a, b, g, s, stride, padding, 1e-5, True),
        (x, BF16), (w, BF16), ((cout,), jnp.float32),
        ((cout,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("x,w,stride,padding", REFUSED_BY_COMPILER)
def test_fused_conv_gate_refuses_what_the_compiler_refused(x, w, stride,
                                                           padding):
    assert not fused_conv.supports(x, w, stride=stride, padding=padding,
                                   itemsize=2)


def test_slot_loop_step_compiles_for_v5e(one_chip, cache_off):
    """The slot loop's hot program (one decode step over 8 slots of the
    full-width GPT, ring cache donated) as the serve phase builds it —
    needs no steering: the Generator hands out its own step function and
    avals.  (The BERT-base TrainStep step takes ~40 s and a stub for
    TrainStep's array placement; CHANGES.md PR 21 reports that rehearsal.)"""
    size = chip_smoke.FULL
    gen = chip_smoke.Generator(chip_smoke._gpt(size, 0),
                               seq_buckets=size.serve_seq_buckets,
                               max_len=size.serve_max_len)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (*gen._state_avals(),
         *gen.step_avals(size.slots, size.serve_max_len)))
    step = gen._build_step(size.slots, size.serve_max_len, -1)
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*avals).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 2 ** 27


F32 = jnp.float32
# GigaChat3.5's delta-rule layer: 32 key heads under 64 value heads, 128 x 128
_DELTA = {"scan_64": (1, 512, 64), "scan_128": (1, 512, 128),
          "update_128_rows": (128, 1, 64)}


@pytest.mark.parametrize("name", sorted(_DELTA))
def test_the_delta_rule_compiles_for_v5e(name, compile_for_chip):
    """The gated delta rule at its published widths (plain XLA: no kernel
    of its own): a 512-token chunk of one row at both scan chunks, and one
    step of 128 rows.  (The chunk's triangular inverse by halves is
    ``log2 L`` rounds of two products of whole ``[L, L]`` matrices a head
    and scan chunk.)"""
    from paddle_tpu.nn.layer.gated_delta import delta_mix
    rows, T, chunk = _DELTA[name]
    G, H, N, P = 32, 64, 128, 128
    text = compile_for_chip(
        lambda q, k, v, a, beta, h: delta_mix(q, k, v, a, beta, h, chunk),
        ((rows, T, G, N), BF16), ((rows, T, G, N), BF16),
        ((rows, T, H, P), BF16), ((rows, T, H), F32), ((rows, T, H), F32),
        ((rows, H, P, N), F32))
    # under the names the trace is read by, and nothing but XLA
    want = "update" if T == 1 else "scan/solve"
    assert want in text and "tpu_custom_call" not in text

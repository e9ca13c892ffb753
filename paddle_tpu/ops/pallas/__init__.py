"""Pallas TPU kernels (the fused-op family of the reference,
/root/reference/paddle/fluid/operators/fused/, rebuilt as on-chip kernels).

Exports ``flash_attention`` working on framework Tensors (tape-autograd via
the Primitive machinery; the kernel carries its own custom VJP) and the pure
array-level ``flash_attention_fn`` for compiled train steps.
"""
from __future__ import annotations

from ...framework.primitive import Primitive
from .flash_attention import (DEFAULT_BLOCK, flash_attention_fn, fused_form,
                              packed_attention_fn, supports, supports_packed)


def _flash_nobias(q, k, v, *, causal=False, scale=None):
    return flash_attention_fn(q, k, v, None, causal=causal, scale=scale)


def _flash_bias(q, k, v, bias, *, causal=False, scale=None):
    return flash_attention_fn(q, k, v, bias, causal=causal, scale=scale)


_flash_prim = Primitive("flash_attention", _flash_nobias)
_flash_bias_prim = Primitive("flash_attention_bias", _flash_bias)


def flash_attention(q, k, v, bias=None, causal=False, scale=None):
    """Flash attention on (B, N, S, H) Tensors; additive ``bias`` optional."""
    if bias is None:
        return _flash_prim(q, k, v, causal=bool(causal), scale=scale)
    return _flash_bias_prim(q, k, v, bias, causal=bool(causal), scale=scale)


def _packed_nobias(q, k, v, *, num_heads, causal=False, scale=None):
    return packed_attention_fn(q, k, v, num_heads, None, causal=causal,
                               scale=scale)


def _packed_bias(q, k, v, bias, *, num_heads, causal=False, scale=None):
    return packed_attention_fn(q, k, v, num_heads, bias, causal=causal,
                               scale=scale)


_packed_prim = Primitive("packed_attention", _packed_nobias)
_packed_bias_prim = Primitive("packed_attention_bias", _packed_bias)


def packed_attention(q, k, v, num_heads, bias=None, causal=False,
                     scale=None):
    """The single-block form on ``[B, S, N*H]`` Tensors (heads side by
    side on the minor dimension); additive ``bias`` optional."""
    kw = dict(num_heads=int(num_heads), causal=bool(causal), scale=scale)
    if bias is None:
        return _packed_prim(q, k, v, **kw)
    return _packed_bias_prim(q, k, v, bias, **kw)


from . import fused_bn, fused_conv  # noqa: F401  (kernel families)
from .latent_attention import (  # noqa: E402
    fused_latent_form, latent_chunk_attention_fn, supports_latent)
from .span_decode import (  # noqa: E402
    span_decode_attention_fn, supports_span_decode)

__all__ = ["flash_attention", "flash_attention_fn", "supports",
           "packed_attention", "packed_attention_fn", "supports_packed",
           "fused_form",
           "latent_chunk_attention_fn", "supports_latent",
           "fused_latent_form",
           "span_decode_attention_fn", "supports_span_decode",
           "DEFAULT_BLOCK", "fused_bn", "fused_conv"]

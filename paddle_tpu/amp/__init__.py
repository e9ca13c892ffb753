"""paddle.amp: automatic mixed precision.

Reference parity: python/paddle/fluid/dygraph/amp/auto_cast.py:91 (amp_guard
with white/black op lists) and loss_scaler.py:27 (AmpScaler / GradScaler);
static side contrib/mixed_precision/decorator.py:36.

TPU-first: bf16 is the native mixed-precision dtype — no loss scaling needed
(bf16 has fp32's exponent range), so O1/O2 map to bf16 compute and
GradScaler degenerates to a pass-through unless fp16 is forced.  The
white/black list machinery survives as the op-level autocast policy consulted
by Primitive dispatch (framework/core.py amp_state).
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from ..framework import core
from ..framework.tensor import Tensor

# fp16_lists.py parity, keyed by REGISTERED primitive names (the paddle op
# names used at Primitive() registration): MXU ops whiten, numerically
# sensitive ops blacken
WHITE_LIST = {"matmul_v2", "mul", "conv2d", "conv2d_nobias",
              "conv2d_transpose", "conv2d_transpose_nobias", "einsum",
              "scaled_dot_product_attention",
              "scaled_dot_product_attention_mask",
              "scaled_dot_product_attention_packed",
              "flash_attention", "flash_attention_bias",
              "packed_attention", "packed_attention_bias", "bilinear_nobias"}
BLACK_LIST = {"exp", "log", "softmax", "log_softmax",
              "softmax_with_cross_entropy", "softmax_with_cross_entropy_soft",
              "layer_norm", "layer_norm_nogb", "batch_norm_train",
              "batch_norm_eval", "reduce_sum", "reduce_mean", "cumsum",
              "elementwise_pow", "p_norm", "frobenius_norm", "bce_loss",
              "kldiv_loss", "log_loss"}
# int8 inference sites (ops/int8.py): autocast must neither down-cast the
# fp32 scale/bias epilogue operands nor up-cast the int8 tensors — the
# integer dot IS the precision contract.  Exempt even under O2.
AMP_EXEMPT = {"linear_int8", "conv2d_int8", "matmul_int8"}


class AmpState:
    def __init__(self, enable=True, dtype="bfloat16", custom_white_list=None,
                 custom_black_list=None, level="O1"):
        self.enable = enable
        self.dtype = jnp.bfloat16 if str(dtype) in ("bfloat16", "bf16") \
            else jnp.float16
        self.level = level
        self.white = (WHITE_LIST | set(custom_white_list or ())) - \
            set(custom_black_list or ())
        self.black = (BLACK_LIST | set(custom_black_list or ())) - \
            set(custom_white_list or ())

    def cast_policy(self, op_name):
        """'low' -> cast fp32 inputs to amp dtype; 'high' -> cast to fp32;
        None -> leave as-is. O2 casts everything but the black list."""
        if not self.enable:
            return None
        if op_name in AMP_EXEMPT:
            return None
        if op_name in self.black:
            return "high"
        if self.level == "O2" or op_name in self.white:
            return "low"
        return None


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast (dygraph amp_guard :91 parity)."""
    state = AmpState(enable, dtype, custom_white_list, custom_black_list,
                     level)
    with core.amp_guard_state(state if enable else None):
        yield


amp_guard = auto_cast


class GradScaler:
    """loss_scaler.py:27 parity.

    With bf16 (TPU default) scaling is mathematically unnecessary: scale()
    and step()/update() pass through at scale 1.  The dynamic-scale state
    machine (incr_every_n_steps / decr on nan) is kept for fp16 use and API
    compatibility (check_finite mirrors check_finite_and_unscale_op,
    operators/amp/).
    """

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling) if enable else 1.0
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False

    def scale(self, loss):
        if not self._enable or self._scale == 1.0:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        if not self._enable:
            return
        import jax.numpy as jnp
        inv = 1.0 / self._scale
        found = False
        for p in (optimizer._parameters or []):
            if p.grad is not None:
                g = p.grad._value * inv
                if not bool(jnp.all(jnp.isfinite(g))):
                    found = True
                p.grad._value = g
        self._found_inf = found

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self._update_scale()

    def minimize(self, optimizer, scaled_loss):
        # Reference contract (loss_scaler.py docstring): the caller runs
        # scaled.backward() first, then minimize().  Only trigger backward
        # here if it hasn't run on THIS loss yet (graph live, no prior
        # backward) — a retain_graph backward must not be re-run, which
        # would double every grad; a fresh un-backwarded loss still works
        # even when grads from earlier micro-batches are being accumulated.
        if scaled_loss._node is not None and not scaled_loss._bwd_done:
            scaled_loss.backward()
        self.step(optimizer)

    def update(self):
        pass  # folded into step()

    def on_step_result(self, found_inf: bool):
        """Drive the dynamic-scale state machine from OUTSIDE the eager
        step()/unscale_() path — the compiled TrainStep's in-graph
        numerics sentinel reports each step's verdict here, so a skipped
        (non-finite) step backs the scale off exactly like the reference's
        update_loss_scaling op, and a good-step streak grows it."""
        self._found_inf = bool(found_inf)
        self._update_scale()

    def _update_scale(self):
        if not self._dynamic:
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def is_enable(self):
        return self._enable

    def get_loss_scaling(self):
        return self._scale

    def state_dict(self):
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps}

    def load_state_dict(self, state):
        self._scale = state["scale"]
        self._good_steps = state["good_steps"]
        self._bad_steps = state["bad_steps"]


AmpScaler = GradScaler


def decorate(models=None, optimizers=None, level="O1", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate parity (contrib/mixed_precision/decorator.py:36).

    O2 on TPU: cast model params to bf16 for storage/compute; the optimizer
    keeps true fp32 master weights (Optimizer._trees seeds an ``@master``
    accumulator the first time it sees a low-precision param, updates the
    master in fp32, and casts back to the stored dtype) — matching the
    reference multi_precision path, so sub-ulp updates are not lost.
    ``master_weight=False`` opts out."""
    if level == "O2" and models is not None:
        targets = models if isinstance(models, (list, tuple)) else [models]
        for m in targets:
            for p in m.parameters():
                if jnp.issubdtype(p._value.dtype, jnp.floating):
                    p._value = p._value.astype(
                        jnp.bfloat16 if dtype in ("bfloat16", "bf16")
                        else jnp.float16)
    if optimizers is not None:
        opts = optimizers if isinstance(optimizers, (list, tuple)) \
            else [optimizers]
        for o in opts:
            o._use_master_weights = master_weight
    if optimizers is None:
        return models
    return models, optimizers

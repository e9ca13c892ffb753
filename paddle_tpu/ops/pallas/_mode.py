"""The one place that decides whether Pallas kernels interpret or compile."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True only on the CPU backend (the tests' reference runs).  On any
    other backend the kernels compile, and a lowering error propagates:
    no caller re-routes a refused kernel to its XLA reference."""
    return jax.default_backend() == "cpu"

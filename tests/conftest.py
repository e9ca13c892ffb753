"""Test env: force CPU backend with 8 virtual devices so distributed tests
exercise real meshes/collectives without TPU hardware (SURVEY.md §4:
multi-node is simulated; here multi-chip is simulated the XLA way).

Both settings go through the process environment, before jax is imported,
so the subprocesses that tests start inherit them.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", "tests must run on CPU backend"


import pytest  # noqa: E402


@pytest.fixture()
def weight_wishes(monkeypatch):
    """``weight_wishes(pick)``: make the free compile of the slot programs
    (``Generator.slot_execs``) ask for the transposed layout of the 2-D
    weights that ``pick(program, name)`` names.  The CPU compiler asks for
    nothing but default layouts, but honours an explicit one, so this is a
    real program that takes no other layout."""
    from jax.experimental.layout import Format, Layout
    from paddle_tpu.text.generation import Generator
    real = Generator._lower

    def install(pick):
        def lower(self, fn, arg_avals, jit_kw, free=False):
            if not free:
                return real(self, fn, arg_avals, jit_kw)
            state = self._state_avals()
            fmts = tuple(
                None if i % 2 else {
                    n: Format(Layout((1, 0), ()), self._state[i][n].sharding)
                    if len(a.shape) == 2 and pick(fn.__name__, n) else None
                    for n, a in tree.items()}
                for i, tree in enumerate(state))
            kw = dict(jit_kw, in_shardings=fmts + (None,) * len(arg_avals))
            return jax.jit(fn, **kw).lower(*state, *arg_avals).compile()
        monkeypatch.setattr(Generator, "_lower", lower)
    return install

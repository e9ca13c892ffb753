"""Graph-lint pass suite tests (paddle_tpu.analysis).

One seeded-violation + one clean fixture per pass, wiring tests for the
three integration points (jit / Executor / TrainStep), flag gating
(off|warn|error), suppression semantics, gauge/JSONL emission, the CLI
over the model zoo in abstract-eval mode, and the flags/ledger satellite
fixes (flags_snapshot, duplicate-registration, weak-type cache-key diff).
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import analysis
from paddle_tpu.analysis import (GraphLintWarning, LintContext, Severity,
                                 default_pass_manager)
from paddle_tpu.framework.enforce import EnforceNotMet
from paddle_tpu.framework.flags import (define_flag, flags_restore,
                                        flags_snapshot, set_flags)
from paddle_tpu.parallel.mesh import MeshGuard, make_mesh

THIS_FILE = os.path.basename(__file__)


def _marker_line(tag):
    """Line number of the '# LINT:<tag>' marker in this file — seeded
    violations assert their diagnostic points at the exact user line."""
    with open(__file__) as f:
        for i, line in enumerate(f, 1):
            if f"# LINT:{tag}" in line:
                return i
    raise AssertionError(f"marker {tag} not found")


def _lint(fn, *args, **ctx):
    closed = jax.make_jaxpr(fn)(*args)
    return analysis.lint_jaxpr(closed, site="test", **ctx)


def _only(report, pass_id):
    return [d for d in report if d.pass_id == pass_id]


@pytest.fixture()
def flags_guard():
    snap = flags_snapshot()
    yield
    flags_restore(snap)


@pytest.fixture()
def clean_stats():
    from paddle_tpu.utils.monitor import reset_stats
    reset_stats("graph_lint")
    yield


# ---------------------------------------------------------------------------
# per-pass seeded + clean fixtures
# ---------------------------------------------------------------------------

def test_recompile_hazard_weak_type_seeded():
    def f(x, s):
        return x * s                                    # LINT:weak
    r = _lint(f, jnp.ones(4), 3.0, arg_paths=["x", "s"])
    found = _only(r, "recompile-hazard")
    assert len(found) == 1
    assert "s is weak-typed" in found[0].message


def test_recompile_hazard_scalar_const_in_key():
    def f(x):
        return x + 1.0
    r = _lint(f, jnp.ones(4),
              cache_key=(("t", (4,), "float32", "strong"),
                         ("c", "float", 0.5)))
    found = _only(r, "recompile-hazard")
    assert len(found) == 1
    assert "0.5" in found[0].message and "new program" in found[0].message


def test_recompile_hazard_ledger_cross_check():
    def f(x):
        return x * 2
    prev = (("arg:inputs[0]", (8, 4), "float32", "strong"),)
    cur = (("arg:inputs[0]", (16, 4), "float32", "strong"),)
    r = _lint(f, jnp.ones((16, 4)), cache_key=cur, prev_key=prev)
    found = _only(r, "recompile-hazard")
    assert len(found) == 1
    assert "recompiled" in found[0].message
    assert "inputs[0]" in found[0].message          # the culprit's path


def test_recompile_hazard_clean():
    def f(x, s):
        return x * s
    r = _lint(f, jnp.ones(4), np.float32(3.0),
              cache_key=(("t", (4,), "float32", "strong"),))
    assert not _only(r, "recompile-hazard")


def test_cache_key_hygiene_seeded(flags_guard):
    """Weak-typed + scalar-baked key leaves fragment the PERSISTENT
    executable cache: one on-disk entry per variant.  The pass fires
    only while FLAGS_executable_cache is on."""
    set_flags({"FLAGS_executable_cache": "read"})

    def f(x):
        return x + 1.0
    r = _lint(f, jnp.ones(4),
              cache_key=(("t", (4,), "float32", "weak"),
                         ("c", "float", 0.5)))
    found = _only(r, "cache-key-hygiene")
    assert len(found) == 2
    msgs = " | ".join(d.message for d in found)
    assert "0.5" in msgs and "executable_cache_dir" in msgs
    assert "weak-typed" in msgs and "one entry" in msgs


def test_cache_key_hygiene_ledger_cross_check(flags_guard):
    set_flags({"FLAGS_executable_cache": "readwrite"})

    def f(x):
        return x * 2
    prev = (("arg:inputs[0]", (8, 4), "float32", "strong"),)
    cur = (("arg:inputs[0]", (16, 4), "float32", "strong"),)
    r = _lint(f, jnp.ones((16, 4)), cache_key=cur, prev_key=prev)
    found = _only(r, "cache-key-hygiene")
    assert len(found) == 1
    assert "churns" in found[0].message
    assert "inputs[0]" in found[0].message


def test_cache_key_hygiene_clean_and_gated(flags_guard):
    def f(x):
        return x + 1
    committed = (("t", (4,), "float32", "strong"),)
    # clean key with the cache on: silent
    set_flags({"FLAGS_executable_cache": "read"})
    assert not _only(_lint(f, jnp.ones(4), cache_key=committed),
                     "cache-key-hygiene")
    # hazardous key with the cache OFF: the pass costs nothing / says
    # nothing — the fragmentation hazard only exists with a cache dir
    set_flags({"FLAGS_executable_cache": "off"})
    assert not _only(_lint(f, jnp.ones(4),
                           cache_key=(("c", "float", 0.5),)),
                     "cache-key-hygiene")


def _twice(a):
    return np.asarray(a) * 2


def test_host_transfer_seeded_with_provenance():
    def f(x):
        sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
        y = jax.pure_callback(_twice, sds, x)           # LINT:host
        return y + x
    r = _lint(f, jnp.ones(4))
    found = _only(r, "host-transfer")
    assert len(found) == 1
    assert found[0].severity == Severity.ERROR
    assert "pure_callback" in found[0].message
    # user-level file:line provenance
    assert THIS_FILE in found[0].location
    assert f":{_marker_line('host')}" in found[0].location


def test_host_transfer_clean():
    def f(x):
        return jnp.tanh(x) + 1
    assert not _only(_lint(f, jnp.ones(4)), "host-transfer")


def test_dtype_promotion_seeded():
    def f(x):
        h = x.astype(jnp.float32)                       # LINT:upcast
        return h @ jnp.ones((16, 16), jnp.float32)
    r = _lint(f, jnp.ones((8, 16), jnp.bfloat16))
    found = _only(r, "dtype-promotion")
    assert len(found) == 1
    assert "bfloat16" in found[0].message
    assert f":{_marker_line('upcast')}" in found[0].location


def test_dtype_promotion_scalar_loss_cast_is_clean():
    # the deliberate fp32 loss accumulation (ndim 0/1) must NOT fire
    def f(x):
        return x.mean().astype(jnp.float32)
    assert not _only(_lint(f, jnp.ones((8, 16), jnp.bfloat16)),
                     "dtype-promotion")


def test_dtype_promotion_f32_graph_clean():
    def f(x):
        return (x @ jnp.ones((16, 16))).astype(jnp.float32)
    assert not _only(_lint(f, jnp.ones((8, 16))), "dtype-promotion")


def test_donation_seeded_and_clean():
    mgr = default_pass_manager()
    params = {"w": np.zeros((4, 4), np.float32)}
    seeded = mgr.run(LintContext(site="s", kind="train_step", donate=False,
                                 params=params))
    found = _only(seeded, "donation")
    assert len(found) == 1 and found[0].severity == Severity.ERROR
    assert "donat" in found[0].message and "2" in found[0].message
    clean = mgr.run(LintContext(site="s", kind="train_step", donate=True,
                                params=params))
    assert not _only(clean, "donation")
    # donation is a train-step concern: other kinds never fire it
    other = mgr.run(LintContext(site="s", kind="jit", donate=False))
    assert not _only(other, "donation")


def test_layout_bad_matmul_padding_seeded():
    def f(x, w):
        return x @ w                                    # LINT:pad
    r = _lint(f, jnp.ones((8, 130)), jnp.ones((130, 8)))
    found = _only(r, "layout")
    assert len(found) == 1
    assert "130" in found[0].message and "256" in found[0].message
    assert f":{_marker_line('pad')}" in found[0].location


def test_layout_minor_dim_dynamic_slice_seeded():
    def f(x, i):
        return jax.lax.dynamic_slice(x, (0, i), (8, 16))  # LINT:dslice
    r = _lint(f, jnp.ones((8, 256)), jnp.int32(3))
    found = _only(r, "layout")
    assert len(found) == 1
    assert "lane" in found[0].message
    assert f":{_marker_line('dslice')}" in found[0].location


def test_layout_clean():
    def f(x, w):
        h = x @ w                        # 128-aligned matmul
        return jax.lax.dynamic_slice(h, (jnp.int32(0), 0), (4, 128))
    r = _lint(f, jnp.ones((8, 128)), jnp.ones((128, 128)))
    # major-dim dynamic slice + aligned matmul: silent
    assert not _only(r, "layout")


def test_layout_lane_dim_dynamic_update_seeded():
    # a traced start on the LANE dim of an update IS a hazard (cross-tile
    # masked scatter) — the KV exemption must not swallow it
    def f(x, v, i):
        return jax.lax.dynamic_update_slice(x, v, (0, i))  # LINT:dupdate
    r = _lint(f, jnp.ones((8, 256)), jnp.ones((8, 16)), jnp.int32(3))
    found = _only(r, "layout")
    assert len(found) == 1
    assert "lane" in found[0].message
    assert f":{_marker_line('dupdate')}" in found[0].location


def test_layout_quantized_kv_scale_read_clean():
    """The int8 cache's read pattern (PR 12): dynamic_slice at a TRACED
    cache position on the sublane (sequence) dim with the lane dim fully
    read — the canonical quantized-KV access (int8 rows and their
    per-head scale planes) is a sublane-masked in-tile load, exempt the
    same way PR 7 exempted the KV write."""
    def f(scales, pos):                    # per-head scale plane read
        return jax.lax.dynamic_slice(scales, (0, 0, pos, 0), (2, 4, 8, 1))
    r = _lint(f, jnp.ones((2, 4, 64, 1)), jnp.int32(3))
    assert not _only(r, "layout")

    def g(k_rows, pos):                    # int8 row-plane read
        return jax.lax.dynamic_slice_in_dim(k_rows, pos, 8, axis=2)
    r2 = _lint(g, jnp.ones((2, 4, 64, 128), jnp.int8), jnp.int32(5))
    assert not _only(r2, "layout")


def test_layout_sublane_dynamic_slice_partial_lane_seeded():
    # the exemption requires the lane dim FULLY read: a partial-lane
    # slice at a traced sublane start is still a cross-tile gather
    def f(x, i):
        return jax.lax.dynamic_slice(x, (0, 0, i, 0), (2, 4, 8, 64))  # LINT:dslice_sub
    r = _lint(f, jnp.ones((2, 4, 64, 128)), jnp.int32(3))
    found = _only(r, "layout")
    assert len(found) == 1
    assert "sublane" in found[0].message
    assert f":{_marker_line('dslice_sub')}" in found[0].location


def test_layout_kv_cache_ring_write_clean():
    # the canonical generate() ring-cache append: dynamic_update_slice at
    # a TRACED cache_position on the sublane (sequence) dim with the lane
    # (head) dim fully spanned — a sublane-masked in-tile store, exempt
    def f(cache, kv, pos):
        return jax.lax.dynamic_update_slice(cache, kv, (0, 0, pos, 0))
    r = _lint(f, jnp.ones((2, 4, 64, 128)), jnp.ones((2, 4, 1, 128)),
              jnp.int32(7))
    assert not _only(r, "layout")
    # the in_dim convenience form paddle.dynamic_update_slice lowers to
    def g(k_cache, k_new, pos):
        return jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, pos,
                                                   axis=2)
    r2 = _lint(g, jnp.ones((1, 2, 32, 128)), jnp.ones((1, 2, 1, 128)),
               jnp.int32(5))
    assert not _only(r2, "layout")


def test_collective_consistency_seeded():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    with MeshGuard(make_mesh({"dp": 8})):
        rogue = Mesh(np.array(jax.devices()).reshape(8), ("rows",))

        def body(x):
            return jax.lax.psum(x, "rows")
        f = shard_map(body, mesh=rogue, in_specs=P("rows"), out_specs=P())
        r = _lint(f, jnp.ones(8))
    found = _only(r, "collective-consistency")
    assert found and found[0].severity == Severity.ERROR
    assert "rows" in found[0].message
    assert THIS_FILE in found[0].location   # user-level provenance


def test_collective_consistency_clean():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh({"dp": 8})
    with MeshGuard(mesh):
        def body(x):
            return jax.lax.psum(x, "dp")
        f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P())
        r = _lint(f, jnp.ones(8))
    assert not _only(r, "collective-consistency")


def test_dead_fetch_seeded():
    def f(x):
        dead = jnp.dot(x, x.T)                          # LINT:dead
        return x + 1
    r = _lint(f, jnp.ones((8, 8)))
    found = _only(r, "dead-fetch")
    assert len(found) == 1
    assert "dot_general" in found[0].message
    assert f":{_marker_line('dead')}" in found[0].location


def test_dead_fetch_clean():
    def f(x):
        return jnp.dot(x, x.T) + 1
    assert not _only(_lint(f, jnp.ones((8, 8))), "dead-fetch")


def test_dead_fetch_program_level():
    mgr = default_pass_manager()
    info = {"ops": [("mul", ("x",), ("y",)),
                    ("add", ("x",), ("z",))],          # z never used
            "fetches": ["y"], "written": [], "persistable": [],
            "feeds": ["x"]}
    r = mgr.run(LintContext(site="exe", kind="executor",
                            program_info=info))
    found = _only(r, "dead-fetch")
    assert len(found) == 1
    assert "'add'" in found[0].message and "z" in str(found[0].extra)
    clean = dict(info, fetches=["y", "z"])
    assert not _only(mgr.run(LintContext(site="exe", kind="executor",
                                         program_info=clean)),
                     "dead-fetch")


def test_sharding_coverage_seeded_and_clean():
    from jax.sharding import PartitionSpec as P
    mgr = default_pass_manager()
    mesh = make_mesh({"dp": 4, "mp": 2})
    params = {"w": np.zeros((8, 8), np.float32),
              "b": np.zeros((8,), np.float32)}
    seeded = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh, params=params,
        partition_specs={"w": None, "b": None}))
    found = _only(seeded, "sharding-coverage")
    assert len(found) == 1           # only the matrix; vectors replicate
    assert "'w'" in found[0].message
    annotated = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh, params=params,
        partition_specs={"w": P(None, "mp"), "b": None}))
    assert not _only(annotated, "sharding-coverage")
    # pure-DP mesh: replication IS the rule, nothing fires
    dp_only = mgr.run(LintContext(
        site="s", kind="train_step", mesh=make_mesh({"dp": 8}),
        params=params, partition_specs={"w": None, "b": None}))
    assert not _only(dp_only, "sharding-coverage")


def test_sharding_coverage_names_autoshard_rule():
    """ISSUE 9: warn-mode coverage output is actionable — each finding
    names the autoshard rule that WOULD shard the leaf (or says no rule
    matches), and a leaf a replication rule explicitly covers is a
    DECIDED layout, not a finding."""
    mgr = default_pass_manager()
    mesh = make_mesh({"dp": 4, "mp": 2})
    params = {
        # matches tp-qkv-column in the default table
        "encoder.layers.0.self_attn.q_proj.weight":
            np.zeros((16, 16), np.float32),
        # matches no rule at all
        "mystery.w": np.zeros((8, 8), np.float32),
        # matches the rec-mlp-replicated P() rule: decided, no finding
        "dnn.0.weight": np.zeros((16, 16), np.float32),
    }
    r = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh, params=params,
        partition_specs={n: None for n in params}))
    found = {d.extra.get("param"): d for d in _only(r, "sharding-coverage")}
    assert set(found) == {"encoder.layers.0.self_attn.q_proj.weight",
                          "mystery.w"}
    named = found["encoder.layers.0.self_attn.q_proj.weight"]
    assert "tp-qkv-column" in named.message
    assert "FLAGS_autoshard=apply" in named.message
    assert named.extra.get("autoshard_rule") == "tp-qkv-column"
    norule = found["mystery.w"]
    assert "no autoshard rule matches" in norule.message
    assert norule.extra.get("autoshard_rule") is None
    # clean fixture: an annotated leaf stays silent regardless of rules
    from jax.sharding import PartitionSpec as P
    clean = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh,
        params={"encoder.layers.0.self_attn.q_proj.weight":
                np.zeros((16, 16), np.float32)},
        partition_specs={"encoder.layers.0.self_attn.q_proj.weight":
                         P(None, "mp")}))
    assert not _only(clean, "sharding-coverage")


def test_sharding_coverage_names_expert_rule():
    """ISSUE 14: an unannotated stacked expert parameter on a mesh with
    a live ep axis is named by the ``moe-expert-ffn`` rule ('FLAGS_
    autoshard=apply closes this'); the gate matches the replication rule
    (a DECIDED layout, no finding); an annotated expert stack is
    silent."""
    mgr = default_pass_manager()
    mesh = make_mesh({"dp": 4, "ep": 2})
    params = {
        "encoder.layers.1.moe.experts.w1": np.zeros((8, 16, 32),
                                                    np.float32),
        "encoder.layers.1.moe.experts.b1": np.zeros((8, 32), np.float32),
        "encoder.layers.1.moe.gate.weight": np.zeros((16, 8), np.float32),
    }
    seeded = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh, params=params,
        partition_specs={n: None for n in params}))
    found = {d.extra.get("param"): d
             for d in _only(seeded, "sharding-coverage")}
    # gate.weight is covered by moe-gate-replicated (pure P()): silent
    assert set(found) == {"encoder.layers.1.moe.experts.w1",
                          "encoder.layers.1.moe.experts.b1"}
    w1 = found["encoder.layers.1.moe.experts.w1"]
    assert "moe-expert-ffn" in w1.message
    assert "P('ep', None, None)" in w1.message
    assert "FLAGS_autoshard=apply closes this" in w1.message
    assert w1.extra.get("autoshard_rule") == "moe-expert-ffn"
    assert found["encoder.layers.1.moe.experts.b1"].extra.get(
        "autoshard_rule") == "moe-expert-bias"
    # clean fixture: the annotated expert stack stays silent
    from jax.sharding import PartitionSpec as P
    clean = mgr.run(LintContext(
        site="s", kind="train_step", mesh=mesh,
        params={"encoder.layers.1.moe.experts.w1":
                np.zeros((8, 16, 32), np.float32)},
        partition_specs={"encoder.layers.1.moe.experts.w1":
                         P("ep", None, None)}))
    assert not _only(clean, "sharding-coverage")


# ---------------------------------------------------------------------------
# dy2static AST lint
# ---------------------------------------------------------------------------

def test_ast_lint_host_transfer_numpy_call():
    def f(x):
        h = x.numpy()                                   # LINT:astnumpy
        return h + 1
    diags = analysis.lint_function_ast(f)
    host = [d for d in diags if d.pass_id == "host-transfer"]
    assert len(host) == 1
    assert THIS_FILE in host[0].location
    assert f":{_marker_line('astnumpy')}" in host[0].location


def test_ast_lint_float_concretization():
    def f(x):
        return float(x) * 2                             # LINT:astfloat
    diags = analysis.lint_function_ast(f)
    rec = [d for d in diags if d.pass_id == "recompile-hazard"]
    assert len(rec) == 1
    assert f":{_marker_line('astfloat')}" in rec[0].location


def test_ast_lint_clean():
    def f(x):
        y = paddle.tanh(x)
        return float("1.5") * y      # literal float(): not a hazard
    assert analysis.lint_function_ast(f) == []


# ---------------------------------------------------------------------------
# flag gating / suppression / emission
# ---------------------------------------------------------------------------

class TinyNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc(x)


def _tiny_step(**kw):
    m = TinyNet()
    opt = paddle.optimizer.SGD(parameters=m.parameters(),
                               learning_rate=1e-2)
    from paddle_tpu.parallel import TrainStep
    return TrainStep(m, opt, loss_fn=nn.CrossEntropyLoss(), **kw)


def _xy(n=8):
    rng = np.random.RandomState(0)
    return rng.randn(n, 16).astype("float32"), rng.randint(0, 4, (n,))


def test_flag_off_is_silent_and_adds_no_work(flags_guard, clean_stats):
    from paddle_tpu.utils.monitor import stat_get
    set_flags({"FLAGS_graph_lint": "off"})
    step = _tiny_step(donate=False)     # seeded donation violation
    x, y = _xy()
    with warnings.catch_warnings():
        warnings.simplefilter("error", GraphLintWarning)
        step(x, y)                      # no warning, no raise
    assert stat_get("graph_lint_warnings") == 0


def test_flag_warn_train_step_donation(flags_guard, clean_stats):
    from paddle_tpu.utils.monitor import stat_get
    set_flags({"FLAGS_graph_lint": "warn"})
    step = _tiny_step(donate=False)
    x, y = _xy()
    with pytest.warns(GraphLintWarning, match="donation"):
        step(x, y)
    assert stat_get("graph_lint_warnings") >= 1
    assert stat_get("graph_lint_donation") >= 1
    # steady state: the cached signature path does not re-lint
    with warnings.catch_warnings():
        warnings.simplefilter("error", GraphLintWarning)
        step(x, y)


def test_flag_error_train_step_donation_raises(flags_guard):
    set_flags({"FLAGS_graph_lint": "error"})
    step = _tiny_step(donate=False)
    x, y = _xy()
    with pytest.raises(EnforceNotMet, match="donation"):
        step(x, y)
    # state never advanced: the violation raised at trace time
    assert int(step.state["step"]) == 0


def test_flag_error_jit_host_transfer_raises(flags_guard):
    set_flags({"FLAGS_graph_lint": "error"})

    @paddle.jit.to_static
    def f(x):
        y = jax.pure_callback(
            _twice, jax.ShapeDtypeStruct((4,), np.float32),
            x._value if hasattr(x, "_value") else x)
        return paddle.to_tensor(y) + x
    with pytest.raises(EnforceNotMet, match="host-transfer"):
        f(paddle.to_tensor(np.ones(4, np.float32)))


def test_flag_warn_jit_clean_fn_no_warning(flags_guard):
    set_flags({"FLAGS_graph_lint": "warn"})

    @paddle.jit.to_static
    def f(x):
        return paddle.tanh(x)
    with warnings.catch_warnings():
        warnings.simplefilter("error", GraphLintWarning)
        out = f(paddle.to_tensor(np.ones((4, 4), np.float32)))
    assert out.shape == [4, 4]


def test_suppression_flag_and_context(flags_guard):
    set_flags({"FLAGS_graph_lint": "error",
               "FLAGS_graph_lint_suppress": "donation"})
    step = _tiny_step(donate=False)
    x, y = _xy()
    step(x, y)                          # suppressed: no raise
    set_flags({"FLAGS_graph_lint_suppress": ""})
    step2 = _tiny_step(donate=False)
    with analysis.suppress("donation"):
        step2(x, y)                     # context-manager suppression
    with pytest.raises(EnforceNotMet, match="donation"):
        _tiny_step(donate=False)(x, y)  # and without it, it still fires


def test_severity_override(flags_guard):
    mgr = default_pass_manager()
    try:
        mgr.set_severity("donation", Severity.WARNING)
        r = mgr.run(LintContext(site="s", kind="train_step", donate=False,
                                params={}))
        assert _only(r, "donation")[0].severity == Severity.WARNING
    finally:
        mgr.set_severity("donation", Severity.ERROR)
    with pytest.raises(KeyError):
        mgr.set_severity("no-such-pass", Severity.ERROR)


def test_executor_wiring_warn_mode(flags_guard):
    set_flags({"FLAGS_graph_lint": "warn"})
    paddle.enable_static()
    try:
        import paddle_tpu.static as static
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [None, 8], "float32")
            h = static.nn.fc(x, 8)
        exe = static.Executor()
        exe.run(startup)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            exe.run(main, feed={"x": np.ones((4, 8), np.float32)},
                    fetch_list=[h])
        # clean single-fetch program: executor lint ran without findings
        assert not [x for x in w if issubclass(x.category,
                                               GraphLintWarning)]
    finally:
        paddle.disable_static()


def test_jsonl_sink_and_gauges(flags_guard, clean_stats, tmp_path):
    from paddle_tpu.utils.monitor import LogWriter, stat_get
    set_flags({"FLAGS_graph_lint": "warn",
               "FLAGS_graph_lint_dir": str(tmp_path)})
    try:
        step = _tiny_step(donate=False)
        x, y = _xy()
        with pytest.warns(GraphLintWarning):
            step(x, y)
        events = LogWriter.read_events(str(tmp_path))
        diags = events.get("graph_lint/diagnostic", [])
        assert diags, "lint diagnostics should stream to JSONL"
        assert any(d["pass"] == "donation" for d in diags)
        assert all("severity" in d and "site" in d for d in diags)
        assert stat_get("graph_lint_donation") >= 1
    finally:
        set_flags({"FLAGS_graph_lint_dir": ""})
        analysis.set_lint_dir(None)     # closes the tmp writer


# ---------------------------------------------------------------------------
# CLI over the model zoo (abstract-eval mode)
# ---------------------------------------------------------------------------

def test_cli_zoo_lints_clean_in_process():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        import graph_lint as gl
    finally:
        sys.path.pop(0)
    for name in gl.ZOO:
        report = gl.lint_model(name)
        assert len(report) == 0, \
            f"zoo model {name} must lint clean, got:\n{report.format()}"


def test_cli_json_and_strict_rc(tmp_path):
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "graph_lint.py"),
         "--model", "lenet", "--strict", "--json"],
        capture_output=True, text=True, cwd=root, timeout=240)
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout)
    assert payload["total_findings"] == 0
    assert payload["models"]["lenet"]["n_errors"] == 0


@pytest.mark.slow
def test_cli_full_zoo_strict_subprocess():
    """CI slow lane: the whole zoo lints clean under --strict."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "graph_lint.py"),
         "--zoo", "--strict"],
        capture_output=True, text=True, cwd=root, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# satellites: flags registry + ledger weak-type/path labeling
# ---------------------------------------------------------------------------

def test_define_flag_duplicate_different_default_raises():
    define_flag("glint_test_flag_a", 3, "t")
    define_flag("glint_test_flag_a", 3, "t")    # same default: idempotent
    with pytest.raises(ValueError, match="different"):
        define_flag("glint_test_flag_a", 4, "t")
    with pytest.raises(ValueError, match="different"):
        define_flag("glint_test_flag_a", 3.0, "t")   # type change too


def test_flags_snapshot_restore_roundtrip():
    define_flag("glint_test_flag_b", 1, "t")
    snap = flags_snapshot()
    assert snap["glint_test_flag_b"] == 1
    set_flags({"glint_test_flag_b": 42})
    assert paddle.get_flags("glint_test_flag_b")["glint_test_flag_b"] == 42
    flags_restore(snap)
    assert paddle.get_flags("glint_test_flag_b")["glint_test_flag_b"] == 1


def test_ledger_diff_names_weak_type_and_path():
    from paddle_tpu.profiler import ledger
    site = "test_graph_lint:weak_path"
    strong = (("arg:inputs[0]", (8, 16), "float32", "strong"),
              ("arg:label", (8,), "int32", "strong"))
    weak = (("arg:inputs[0]", (8, 16), "float32", "weak"),
            ("arg:label", (8,), "int32", "strong"))
    ledger.record_compile(site, "train_step", strong, 1.0)
    assert ledger.last_key(site) == strong
    ev = ledger.record_compile(site, "train_step", weak, 1.0)
    diff = "\n".join(ev["diff"])
    assert "inputs[0]" in diff          # the argument path
    assert "weak" in diff               # the weak-type bit
    assert "label" not in diff          # unchanged args stay out


def test_train_step_sig_carries_path_and_weak_bit():
    from paddle_tpu.profiler import ledger
    step = _tiny_step()
    x, y = _xy()
    step(x, y)
    site = [s for s in (e["site"] for e in ledger.compile_events())
            if s.startswith("train_step:TinyNet")][-1]
    ev = [e for e in ledger.compile_events(site)][-1]
    assert "inputs[0]" in ev["key"] and "strong" in ev["key"]
    # a retrace on a NEW batch shape diffs the exact argument
    x2 = np.random.RandomState(1).randn(16, 16).astype("float32")
    y2 = np.random.RandomState(1).randint(0, 4, (16,))
    step(x2, y2)
    ev2 = ledger.compile_events(site)[-1]
    assert any("inputs[0]" in line for line in ev2["diff"])

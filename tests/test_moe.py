"""Expert-parallel Mixture-of-Experts (ISSUE 14).

Covers: the routing-layer expert movers (capacity, dispatch plan,
routed vs dense-buffer exactness), top-k gating + aux load-balance
loss, the MoELayer REQUIRED GATE — routed forward/backward bit-matches
the GShard dense-dispatch control on the 8-device mesh at top-k 1 and 2,
including multi-step jitted TrainStep trajectories of GPTMoEModel —
decode through generate() (tokens identical to the control, two
executables), serving-decode zero-steady-recompile composition, the
autoshard ``expert`` rules head, the typed drop/load metrics, the
persistent-cache program identity (no false hits across
n_experts/top_k/capacity), and the new flags' validator/idempotence/
snapshot coverage.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.framework.enforce import InvalidArgumentError
from paddle_tpu.framework.flags import (define_flag, flags_restore,
                                        flags_snapshot, set_flags)
from paddle_tpu.framework.functional import functional_call, layer_state
from paddle_tpu.nn.layer.moe import (MoEEncoderLayer, MoELayer,
                                     gate_from_logits, load_balance_loss,
                                     moe_layers, publish_moe_metrics,
                                     top_k_gating, total_aux_loss)
from paddle_tpu.ops import routing as R
from paddle_tpu.parallel import TrainStep
from paddle_tpu.parallel.mesh import EP_AXIS, make_mesh
from paddle_tpu.profiler import ledger
from paddle_tpu.text.models.gpt import GPTMoEConfig, GPTMoEModel

N_DEV = 8


def _mesh():
    return make_mesh({"ep": N_DEV})


@pytest.fixture()
def flags_guard():
    snap = flags_snapshot()
    yield
    flags_restore(snap)


# ---------------------------------------------------------------------------
# routing primitives
# ---------------------------------------------------------------------------

def test_moe_capacity():
    # ceil(cf * tokens * k / E), floored at 1
    assert R.moe_capacity(32, 2, 8, 1.0) == 8
    assert R.moe_capacity(32, 2, 8, 1.25) == 10
    assert R.moe_capacity(4, 2, 64, 1.25) == 1
    assert R.moe_capacity(1, 1, 128, 0.5) == 1


def test_expert_dispatch_plan_matches_numpy_reference():
    rng = np.random.RandomState(0)
    G, S, E, cap = 4, 24, 8, 4
    eids = rng.randint(0, E, (G, S)).astype(np.int32)
    plan = R.expert_dispatch_plan(jnp.asarray(eids), n_experts=E, cap=cap)
    pos = np.asarray(plan.pos)
    counts = np.asarray(plan.counts)
    dropped = np.asarray(plan.dropped)
    for g in range(G):
        fill = {e: 0 for e in range(E)}
        n_drop = 0
        for t in range(S):
            e = int(eids[g, t])
            if fill[e] < cap:
                # kept: slot = e*cap + arrival rank within the expert
                assert pos[g, t] == e * cap + fill[e], (g, t)
                fill[e] += 1
            else:
                assert pos[g, t] == -1
                n_drop += 1
        assert dropped[g] == n_drop
        for e in range(E):
            assert counts[g, e] == int((eids[g] == e).sum())
    # kept slots are unique per group
    for g in range(G):
        kept = pos[g][pos[g] >= 0]
        assert len(set(kept.tolist())) == len(kept)


def test_expert_dispatch_plan_sentinels_never_consume_cap():
    eids = jnp.asarray([[0, -1, 0, -1, 0, 0]], jnp.int32)
    plan = R.expert_dispatch_plan(eids, n_experts=2, cap=4)
    assert int(plan.dropped[0]) == 0
    assert int(plan.counts[0, 0]) == 4
    assert (np.asarray(plan.pos)[0][np.asarray(eids)[0] < 0] == -1).all()


def test_local_experts_routes_compute_and_masks():
    """Meshless scatter → stacked FFN → gather equals a hand loop."""
    rng = np.random.RandomState(1)
    E, cap, D = 4, 3, 8
    S = 10
    eids = rng.randint(0, E, (1, S)).astype(np.int32)
    x = rng.randn(S, D).astype(np.float32)
    plan = R.expert_dispatch_plan(jnp.asarray(eids), n_experts=E, cap=cap)
    w = rng.randn(E, D, D).astype(np.float32)

    def fn(rows, w):
        return jnp.einsum("emd,edh->emh", rows, w)

    got = np.asarray(R.local_experts(jnp.asarray(x), plan.pos, [jnp.asarray(w)],
                                     fn, n_experts=E, cap=cap))
    pos = np.asarray(plan.pos)[0]
    # the movement (scatter, gather, mask) is exact: the same contraction
    # over a buffer filled by hand returns each kept row to the bit
    rows = np.zeros((E * cap, D), np.float32)
    rows[pos[pos >= 0]] = x[pos >= 0]
    by_hand = np.asarray(fn(jnp.asarray(rows.reshape(E, cap, D)),
                            jnp.asarray(w))).reshape(E * cap, D)
    eps = np.finfo(np.float32).eps
    for t in range(S):
        if pos[t] < 0:
            assert np.array_equal(got[t], np.zeros(D, np.float32))
            continue
        np.testing.assert_array_equal(got[t], by_hand[pos[t]])
        # the arithmetic, against numpy: XLA:CPU's batched contraction and
        # BLAS add the D products in different orders, so the two agree to
        # the rounding bound of a length-D float32 dot product (each side
        # within D * eps * sum|x||w| of the exact value; seen: 1.0 eps),
        # not to the bit.  A wrong expert or row is off by O(1).
        we = w[int(eids[0, t])]
        bound = 2 * D * eps * (np.abs(x[t]) @ np.abs(we))
        assert (np.abs(got[t] - x[t] @ we) <= bound).all(), t


def test_moe_a2a_wire_bytes_model():
    assert R.moe_a2a_wire_bytes(8, 4, 16, 1) == 0
    # two legs of the [E, cap, D] buffer, (n-1)/n crossing the wire
    assert R.moe_a2a_wire_bytes(8, 4, 16, 8) == int(2 * 8 * 4 * 16 * 4 * 7 / 8)


def test_all_to_all_experts_equals_local_on_mesh():
    """The routed mover over the 8-shard mesh returns exactly the rows a
    per-group local dispatch computes (same plan, same expert stacks)."""
    mesh = _mesh()
    rng = np.random.RandomState(2)
    E, D, H, U, k = 8, 8, 16, 64, 1
    u = U // N_DEV
    cap = R.moe_capacity(u, k, E, 1.25)
    eids = rng.randint(0, E, (N_DEV, u * k)).astype(np.int32)
    x = rng.randn(U * k, D).astype(np.float32)
    w1 = (rng.randn(E, D, H) * 0.1).astype(np.float32)
    w2 = (rng.randn(E, H, D) * 0.1).astype(np.float32)

    def fn(rows, w1, w2):
        return jnp.einsum("emh,ehd->emd",
                          jnp.einsum("emd,edh->emh", rows, w1), w2)

    plan = R.expert_dispatch_plan(jnp.asarray(eids), n_experts=E, cap=cap)
    routed = np.asarray(R.all_to_all_experts(
        jnp.asarray(x), plan.pos, [jnp.asarray(w1), jnp.asarray(w2)], fn,
        mesh=mesh, axis="ep", n_experts=E, cap=cap))
    # reference: run each group through its own local dispatch, but with
    # per-expert row batches CONCATENATED across groups (what the mesh
    # exchange produces) — row-wise math makes the values identical
    for g in range(N_DEV):
        pg = R.expert_dispatch_plan(jnp.asarray(eids[g:g + 1]),
                                    n_experts=E, cap=cap)
        local = np.asarray(R.local_experts(
            jnp.asarray(x[g * u * k:(g + 1) * u * k]), pg.pos,
            [jnp.asarray(w1), jnp.asarray(w2)], fn, n_experts=E, cap=cap))
        np.testing.assert_array_equal(routed[g * u * k:(g + 1) * u * k],
                                      local)


def test_all_to_all_experts_validates_divisibility():
    mesh = _mesh()
    with pytest.raises(ValueError, match="divisible"):
        R.all_to_all_experts(jnp.zeros((8, 4)), jnp.zeros((8, 1), jnp.int32),
                             [jnp.zeros((12, 4, 4))], lambda r, w: r,
                             mesh=mesh, axis="ep", n_experts=12, cap=1)


# ---------------------------------------------------------------------------
# gating + aux loss
# ---------------------------------------------------------------------------

def test_top_k_gating_k1_and_k2():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    probs, eids, gates = top_k_gating(x, w, 1)
    assert probs.shape == (16, 4) and eids.shape == (16, 1)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(eids)[:, 0],
                                  np.asarray(probs).argmax(-1))
    # k=1 keeps the raw top-1 probability (Switch rule)
    np.testing.assert_array_equal(np.asarray(gates)[:, 0],
                                  np.asarray(probs).max(-1))
    probs2, eids2, gates2 = top_k_gating(x, w, 2)
    # top-2 renormalizes over the chosen pair
    np.testing.assert_allclose(np.asarray(gates2).sum(-1), 1.0, rtol=1e-6)
    assert (np.asarray(eids2)[:, 0] != np.asarray(eids2)[:, 1]).all()
    with pytest.raises(InvalidArgumentError):
        gate_from_logits(jnp.zeros((4, 4)), 3)


def test_load_balance_loss_uniform_is_minimal():
    E, U = 8, 64
    probs = jnp.full((U, E), 1.0 / E, jnp.float32)
    eids = jnp.asarray(np.arange(U) % E, jnp.int32)[:, None]
    aux = float(load_balance_loss(probs, eids, 1))
    np.testing.assert_allclose(aux, 1.0, rtol=1e-6)
    # collapsing every token onto one expert maximizes the loss (E)
    eids_bad = jnp.zeros((U, 1), jnp.int32)
    probs_bad = jnp.zeros((U, E), jnp.float32).at[:, 0].set(1.0)
    np.testing.assert_allclose(float(load_balance_loss(probs_bad, eids_bad,
                                                       1)), E, rtol=1e-6)


def test_load_balance_loss_matches_handroll_groups():
    rng = np.random.RandomState(4)
    E, G, u, k = 4, 2, 8, 2
    probs = jax.nn.softmax(jnp.asarray(rng.randn(G * u, E), jnp.float32))
    eids = jnp.asarray(rng.randint(0, E, (G * u, k)), jnp.int32)
    got = float(load_balance_loss(probs, eids, G))
    pn, en = np.asarray(probs), np.asarray(eids)
    acc = 0.0
    for g in range(G):
        pg = pn[g * u:(g + 1) * u]
        eg = en[g * u:(g + 1) * u].reshape(-1)
        mean_gate = pg.mean(0)
        frac = np.asarray([(eg == e).mean() for e in range(E)])
        acc += E * float((frac * mean_gate).sum())
    np.testing.assert_allclose(got, acc / G, rtol=1e-5)


# ---------------------------------------------------------------------------
# MoELayer: the bit-match gate
# ---------------------------------------------------------------------------

def _layer_pair(k, mesh, d=16, h=32, e=8, cf=1.25):
    paddle.seed(0)
    routed = MoELayer(d, h, e, top_k=k, capacity_factor=cf, mesh=mesh,
                      axis="ep", dispatch="routed")
    paddle.seed(0)
    dense = MoELayer(d, h, e, top_k=k, capacity_factor=cf, mesh=mesh,
                     axis="ep", dispatch="dense", annotate=False)
    return routed, dense


@pytest.mark.parametrize("k", [1, 2])
def test_layer_routed_bitmatches_dense_control_fwd_bwd(k):
    """REQUIRED GATE (layer): the routed all-to-all dispatch bit-matches
    the GShard dense-dispatch control on the 8-device mesh — output AND
    every gradient (params + input), eager and jitted; the jitted bias
    gradients to a few spacings (below)."""
    mesh = _mesh()
    routed, dense = _layer_pair(k, mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 16).astype(np.float32))
    ct = jnp.asarray(rng.randn(64, 16).astype(np.float32))

    def mk(m):
        p, b = layer_state(m)
        def loss(p, x):
            out, _ = functional_call(m, p, b, (x,), training=False,
                                     mutable_buffers=True)
            return jnp.vdot(out, ct) + m.aux_loss()
        return p, loss

    pr, fr = mk(routed)
    pd, fd = mk(dense)
    # forward (+ aux) bitwise
    assert float(fr(pr, x)) == float(fd(pd, x))
    for jitted in (False, True):
        def grads(f, p):
            g = jax.grad(f, argnums=(0, 1))
            return (jax.jit(g) if jitted else g)(p, x)
        gr = grads(fr, pr)
        gd = grads(fd, pd)
        np.testing.assert_array_equal(np.asarray(gr[1]), np.asarray(gd[1]))
        for name in gr[0]:
            a, b = np.asarray(gr[0][name]), np.asarray(gd[0][name])
            if jitted and name in ("experts.b1", "experts.b2"):
                # A bias gradient is the sum of an expert's buffer rows.
                # Compiled, the routed program and the dense control fuse
                # that reduction differently and add the same rows in
                # another order (XLA:CPU, whatever the thread count): b1
                # differs at k=1, b1 and b2 at k=2, by at most 1.5 float32
                # spacings at the leaf's largest entry.  4 spacings; every
                # other leaf, the input gradient and the eager gradients
                # stay equal to the bit.
                np.testing.assert_allclose(
                    a, b, rtol=0, atol=4 * np.spacing(np.abs(b).max()),
                    err_msg=name)
            else:
                assert np.array_equal(a, b), name


def test_layer_local_fallback_no_mesh():
    """Without the expert axis the layer runs the meshless dispatch —
    same math, no collectives; dense control agrees bitwise."""
    paddle.seed(0)
    routed = MoELayer(8, 16, 4, top_k=2, capacity_factor=1.5, mesh=None,
                      axis="ep", dispatch="routed")
    assert routed.n_shards == 1
    paddle.seed(0)
    dense = MoELayer(8, 16, 4, top_k=2, capacity_factor=1.5, mesh=None,
                     axis="ep", dispatch="dense")
    x = paddle.to_tensor(np.random.RandomState(1).randn(12, 8)
                         .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(routed(x)._value),
                                  np.asarray(dense(x)._value))


def test_layer_drop_counting_and_load_buffers():
    paddle.seed(0)
    m = MoELayer(8, 16, 4, top_k=1, capacity_factor=0.25, mesh=None)
    x = paddle.to_tensor(np.random.RandomState(2).randn(16, 8)
                         .astype(np.float32))
    m(x)
    dropped = float(np.asarray(m._moe_dropped._value))
    load = np.asarray(m._moe_load._value)
    # cap = ceil(0.25 * 16 / 4) = 1 slot/expert: at most 4 kept of 16
    assert dropped == 16 - 4
    assert load.shape == (4,)
    # load ratios are counts * E / (U*k): they sum to E over experts
    np.testing.assert_allclose(load.sum(), 4.0, rtol=1e-6)
    # dropped assignments contribute zero rows (residual passthrough is
    # the surrounding block's add): with cap=1/expert at most 4 rows of
    # the combine are non-zero
    out = np.asarray(m(x)._value)
    assert (np.abs(out).sum(axis=1) > 0).sum() <= 4


def test_layer_validation():
    mesh = _mesh()
    with pytest.raises(InvalidArgumentError, match="divide"):
        MoELayer(8, 16, 6, mesh=mesh, axis="ep")      # 6 % 8 != 0
    with pytest.raises(InvalidArgumentError, match="top_k"):
        MoELayer(8, 16, 8, top_k=3)
    with pytest.raises(InvalidArgumentError, match="capacity_factor"):
        MoELayer(8, 16, 8, capacity_factor=0.0)
    with pytest.raises(InvalidArgumentError, match="dispatch"):
        MoELayer(8, 16, 8, dispatch="magic")
    m = MoELayer(8, 16, 8, top_k=1, mesh=mesh, axis="ep")
    with pytest.raises(InvalidArgumentError, match="divisible"):
        m(paddle.to_tensor(np.zeros((3, 8), np.float32)))  # 3 % 8


def test_layer_annotates_expert_stack():
    from paddle_tpu.parallel.api import get_partition_spec
    mesh = _mesh()
    m = MoELayer(16, 32, 8, mesh=mesh, axis="ep")
    assert get_partition_spec(m.experts.w1) == P("ep", None, None)
    assert get_partition_spec(m.experts.b1) == P("ep", None)
    assert get_partition_spec(m.experts.w2) == P("ep", None, None)
    # gate replicates by design: no annotation
    assert get_partition_spec(m.gate.weight) is None


# ---------------------------------------------------------------------------
# GPTMoEModel: training trajectory gate + decode
# ---------------------------------------------------------------------------

def _model_pair(k, mesh, layers=4, experts=8):
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=layers,
                            heads=2, seq=32, experts=experts, top_k=k,
                            capacity_factor=1.25)
    cfg.dropout = 0.0

    def build(dispatch):
        paddle.seed(0)
        m = GPTMoEModel(cfg, mesh=mesh, dispatch=dispatch,
                        annotate=(dispatch == "routed"))
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=1e-3)
        return m, TrainStep(m, opt, mesh=mesh)
    return build("routed"), build("dense")


@pytest.mark.parametrize("k", [1, 2])
def test_trainstep_trajectory_bitmatches_dense_control(k):
    """REQUIRED GATE (model): 3 jitted TrainStep steps of GPT-MoE on the
    8-device mesh — losses AND every parameter bit-identical to the
    dense-dispatch control, so gradients are bit-identical too (any
    grad skew would compound through AdamW within a step)."""
    mesh = _mesh()
    (mr, sr), (md, sd) = _model_pair(k, mesh)
    ids = np.random.RandomState(0).randint(0, 64, (8, 32))
    losses = []
    for _ in range(3):
        lr = float(np.asarray(sr((jnp.asarray(ids), jnp.asarray(ids)),
                                 None)))
        ld = float(np.asarray(sd((jnp.asarray(ids), jnp.asarray(ids)),
                                 None)))
        assert lr == ld
        losses.append(lr)
    assert losses[-1] < losses[0]        # it actually trains
    for name in sr.state["params"]:
        assert np.array_equal(
            np.asarray(jax.device_get(sr.state["params"][name])),
            np.asarray(jax.device_get(sd.state["params"][name]))), name


def test_model_loss_carries_aux_term(flags_guard):
    mesh = _mesh()
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                            heads=2, seq=32, experts=8, top_k=2,
                            capacity_factor=1.25)
    cfg.dropout = 0.0
    paddle.seed(0)
    m = GPTMoEModel(cfg, mesh=mesh)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 64, (8, 16)))
    m.eval()
    loss = m(ids, ids)
    aux = float(np.asarray(jax.device_get(m.moe_aux_loss())))
    assert aux >= 1.0 - 1e-5             # E·Σ f·P is minimal at 1
    # the model loss is CE + aux_weight * aux (CE recoverable exactly)
    logits = m(ids)
    from paddle_tpu.nn import functional as F
    ce = F.cross_entropy(
        logits[:, :-1].reshape([-1, cfg.vocab_size]),
        ids[:, 1:].reshape([-1])).mean()
    np.testing.assert_allclose(
        float(np.asarray(loss._value)),
        float(np.asarray(ce._value)) + cfg.moe_aux_weight * aux,
        rtol=1e-6)
    assert len(moe_layers(m)) == cfg.num_layers // cfg.moe_every
    assert float(np.asarray(jax.device_get(total_aux_loss(m)))) == aux


def test_generate_tokens_identical_to_dense_control():
    """Decode composes unchanged: greedy generate() through the MoE
    stack emits tokens bit-identical to the dense-dispatch control, as
    exactly two executables (prefill + scanned decode)."""
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                            heads=2, seq=64, experts=4, top_k=2,
                            capacity_factor=1.25)
    cfg.dropout = 0.0
    paddle.seed(0)
    mr = GPTMoEModel(cfg, dispatch="routed")     # meshless local dispatch
    paddle.seed(0)
    md = GPTMoEModel(cfg, dispatch="dense")
    ids = np.random.RandomState(0).randint(1, 64, (2, 12))
    ledger.clear()
    tr = mr.generate(paddle.to_tensor(ids), max_new_tokens=8)
    td = md.generate(paddle.to_tensor(ids), max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(tr._value),
                                  np.asarray(td._value))
    evs = ledger.compile_events("generate:gptmoemodel")
    assert [e["kind"] for e in evs] == ["generate_prefill",
                                       "generate_decode"] * 2
    # repeat: ledgered cache hits, zero fresh executables
    mr.generate(paddle.to_tensor(ids), max_new_tokens=8)
    assert len(ledger.compile_events("generate:gptmoemodel")) == 4


def test_serving_decode_zero_steady_recompiles():
    """GPT-MoE through the serving decode engine: warm-up compiles the
    grid, mixed traffic stays recompile-free, served tokens bit-match a
    standalone batch-1 generate()."""
    from paddle_tpu import serving
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                            heads=2, seq=64, experts=4, top_k=2,
                            capacity_factor=1.25)
    cfg.dropout = 0.0
    paddle.seed(7)
    m = GPTMoEModel(cfg)
    m.eval()
    srv = serving.Server(serving.ServingConfig(workers=2))
    srv.register_decode("gpt_moe", m, batch_buckets=(1, 2),
                        seq_buckets=(8, 16), max_new_tokens=4, max_len=32)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 64, rng.randint(2, 14))
                   for _ in range(5)]
        outs = [srv.run_decode("gpt_moe", [p], max_new_tokens=4)[0]
                for p in prompts]
        srv.assert_zero_steady_state_recompiles()
        paddle.seed(7)
        ctrl = GPTMoEModel(cfg)
        ctrl.eval()
        for p, out in zip(prompts, outs):
            ref = ctrl.generate(paddle.to_tensor(p[None, :]),
                                max_new_tokens=4)
            np.testing.assert_array_equal(out[0], np.asarray(ref._value)[0])
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# compile-time stack: autoshard rules, persistent cache identity
# ---------------------------------------------------------------------------

def test_expert_rules_table(flags_guard):
    from paddle_tpu.analysis.autoshard import (expert_rules, rules_table,
                                               rules_table_names)
    assert "expert" in rules_table_names()
    t = rules_table("expert")
    assert t.spec_for("encoder.layers.1.moe.experts.w1",
                      (8, 16, 32)) == P("ep", None, None)
    assert t.spec_for("encoder.layers.1.moe.experts.b2",
                      (8, 16)) == P("ep", None)
    assert t.spec_for("encoder.layers.1.moe.gate.weight", (16, 8)) == P()
    # the table reads FLAGS_moe_axis at construction (EP=DP meshes)
    set_flags({"FLAGS_moe_axis": "dp"})
    assert expert_rules().spec_for("experts.w1",
                                   (8, 4, 4)) == P("dp", None, None)


def test_autoshard_apply_closes_unannotated_experts(flags_guard):
    from paddle_tpu.analysis import autoshard
    mesh = _mesh()
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=2,
                            heads=2, seq=32, experts=8, top_k=2,
                            capacity_factor=1.25)
    paddle.seed(0)
    m = GPTMoEModel(cfg, mesh=mesh, annotate=False)
    plan = autoshard.propose(m, mesh=mesh)
    by_name = {e.name: e for e in plan.sharded}
    assert by_name["encoder.layers.1.moe.experts.w1"].rule \
        == "moe-expert-ffn"
    assert by_name["encoder.layers.1.moe.experts.b1"].rule \
        == "moe-expert-bias"
    autoshard.apply(m, plan=plan, mesh=mesh)
    from paddle_tpu.parallel.api import get_partition_spec
    assert get_partition_spec(
        m.encoder.layers[1].moe.experts.w1) == P(EP_AXIS, None, None)


def test_generator_program_identity_keys_moe_settings():
    """Persistent-cache false-hit guard: the Generator's program
    identity (hashed into the on-disk digest) must differ across
    n_experts / top_k / capacity_factor — flag-resolved fields included,
    because GPTMoEModel resolves them into its config at construction."""
    from paddle_tpu.text.generation import Generator

    def ident(experts, k, cf):
        cfg = GPTMoEConfig.tiny(vocab_size=32, hidden_size=16, layers=2,
                                heads=2, seq=32, experts=experts, top_k=k,
                                capacity_factor=cf)
        cfg.dropout = 0.0
        paddle.seed(0)
        return Generator(GPTMoEModel(cfg),
                         seq_buckets=(8, 16), max_len=32)._program_identity()

    base = ident(4, 2, 1.25)
    assert base != ident(8, 2, 1.25)
    assert base != ident(4, 1, 1.25)
    assert base != ident(4, 2, 1.0)
    assert base == ident(4, 2, 1.25)


def test_moe_grid_warm_start_cache_load(tmp_path, flags_guard):
    """The MoE decode grid round-trips the persistent executable cache:
    a second Generator over the same architecture loads every
    executable as kind cache_load with bit-identical tokens; a
    different expert count never false-hits."""
    import os
    from paddle_tpu.text.generation import Generator
    d = str(tmp_path / "exec_cache")
    os.makedirs(d)
    set_flags({"FLAGS_executable_cache": "readwrite",
               "FLAGS_executable_cache_dir": d})

    def gen(experts, site):
        cfg = GPTMoEConfig.tiny(vocab_size=32, hidden_size=16, layers=2,
                                heads=2, seq=32, experts=experts, top_k=2,
                                capacity_factor=1.25)
        cfg.dropout = 0.0
        paddle.seed(0)
        return Generator(GPTMoEModel(cfg), site=site,
                         seq_buckets=(8, 16), max_len=32)

    ids = np.random.RandomState(1).randint(1, 32, (1, 6))
    out1 = np.asarray(gen(4, "generate:moe_ec1")
                      .generate(paddle.to_tensor(ids), max_new_tokens=3))
    g2 = gen(4, "generate:moe_ec2")
    out2 = np.asarray(g2.generate(paddle.to_tensor(ids), max_new_tokens=3))
    kinds2 = [e["kind"] for e in ledger.compile_events("generate:moe_ec2")]
    assert kinds2 and all(kk == "cache_load" for kk in kinds2), kinds2
    np.testing.assert_array_equal(out1, out2)
    g3 = gen(8, "generate:moe_ec3")
    g3.generate(paddle.to_tensor(ids), max_new_tokens=3)
    kinds3 = [e["kind"] for e in ledger.compile_events("generate:moe_ec3")]
    assert any(kk != "cache_load" for kk in kinds3), kinds3


def test_forward_census_two_all_to_alls_per_moe_block():
    """The architectural census invariant: the compiled FORWARD program
    carries exactly two all-to-alls per MoE block (tokens out, results
    back)."""
    from paddle_tpu.analysis import hlo as H
    from paddle_tpu.parallel.api import named_shardings
    from paddle_tpu.framework.functional import functionalize
    from jax.sharding import NamedSharding
    mesh = _mesh()
    cfg = GPTMoEConfig.tiny(vocab_size=64, hidden_size=16, layers=4,
                            heads=2, seq=32, experts=8, top_k=2,
                            capacity_factor=1.25)
    cfg.dropout = 0.0
    paddle.seed(0)
    m = GPTMoEModel(cfg, mesh=mesh)
    apply_fn, params, bufs = functionalize(m, training=False)
    sh = named_shardings(m, mesh)
    rep = NamedSharding(mesh, P())
    pp = {n: jax.device_put(v, sh.get(n, rep)) for n, v in params.items()}
    bb = {n: jax.device_put(v, rep) for n, v in bufs.items()}
    ids = jax.device_put(
        jnp.asarray(np.random.RandomState(0).randint(0, 64, (8, 32))), rep)
    compiled = jax.jit(lambda p, b, i: apply_fn(p, b, i)) \
        .lower(pp, bb, ids).compile()
    stats = H.program_stats(compiled)
    n_moe = cfg.num_layers // cfg.moe_every
    assert int(stats.collectives["all-to-all"]["count"]) == 2 * n_moe
    # wire bytes ∝ capacity: the ring model predicts each leg exactly
    layer = m.encoder.layers[1].moe
    predicted = layer.wire_bytes(8 * 32) * n_moe
    assert stats.collectives["all-to-all"]["wire_bytes"] == predicted


# ---------------------------------------------------------------------------
# metrics + flags
# ---------------------------------------------------------------------------

def test_publish_moe_metrics_counts():
    from paddle_tpu.profiler.metrics import default_registry
    paddle.seed(0)
    m = MoELayer(8, 16, 4, top_k=1, capacity_factor=0.25, mesh=None)
    x = paddle.to_tensor(np.random.RandomState(2).randn(16, 8)
                         .astype(np.float32))
    m(x)
    reg = default_registry()
    c = reg.get("moe_tokens_dropped_total")
    h = reg.get("moe_expert_load_ratio")
    before_c = c.labels(model="t_moe").value
    before_h = h.labels(model="t_moe").count
    dropped, loads = publish_moe_metrics(m, model="t_moe")
    assert dropped == 12.0 and len(loads) == 4
    assert c.labels(model="t_moe").value == before_c + 12.0
    assert h.labels(model="t_moe").count == before_h + 4


def test_moe_flags_validators_and_snapshot(flags_guard):
    from paddle_tpu.framework.flags import flag
    # defaults: dense FFN everywhere — the flags only feed unset fields
    assert flag("moe_top_k") == 2
    assert flag("moe_capacity_factor") == 1.25
    assert flag("moe_axis") == "ep"
    for bad in ({"FLAGS_moe_top_k": 3}, {"FLAGS_moe_top_k": 0},
                {"FLAGS_moe_capacity_factor": 0.0},
                {"FLAGS_moe_axis": "xx"}):
        with pytest.raises(ValueError):
            set_flags(bad)
    set_flags({"FLAGS_moe_top_k": 1, "FLAGS_moe_capacity_factor": 2.0,
               "FLAGS_moe_axis": "dp"})
    m = MoELayer(8, 16, 8, mesh=None)       # unset fields read the flags
    assert m.top_k == 1 and m.capacity_factor == 2.0 and m.axis == "dp"
    snap = flags_snapshot()
    set_flags({"FLAGS_moe_top_k": 2})
    flags_restore(snap)
    assert flag("moe_top_k") == 1
    # idempotent re-registration (module reload); different default raises
    define_flag("moe_top_k", 2, "dup")
    with pytest.raises(ValueError):
        define_flag("moe_top_k", 4, "dup")


def test_gptmoe_config_resolves_flags_at_construction(flags_guard):
    set_flags({"FLAGS_moe_top_k": 1, "FLAGS_moe_capacity_factor": 2.0})
    cfg = GPTMoEConfig.tiny(vocab_size=32, hidden_size=16, layers=2,
                            heads=2, seq=32, experts=4)
    assert cfg.moe_top_k is None
    paddle.seed(0)
    m = GPTMoEModel(cfg)
    # resolved INTO the config: the program identity names the real knobs
    assert m.config.moe_top_k == 1
    assert m.config.moe_capacity_factor == 2.0
    assert m.encoder.layers[1].moe.top_k == 1


def test_moe_encoder_layer_ring_cache_contract():
    paddle.seed(0)
    blk = MoEEncoderLayer(16, 2, 32, 4, dropout=0.0, top_k=2,
                          capacity_factor=1.25)
    cache = blk.gen_ring_cache(2, 8)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 1, 16)
                         .astype(np.float32))
    out, new_cache = blk(x, None, cache=cache,
                         cache_position=paddle.to_tensor(np.int32(0)))
    assert tuple(out.shape) == (2, 1, 16)
    assert new_cache.k.shape == cache.k.shape

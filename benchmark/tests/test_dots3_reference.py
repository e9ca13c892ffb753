"""The plain reference of the latent-attention MoE decoder against what
does not depend on it: the share of the experts adds up to the uncut layer,
the operation and byte counts equal hand counts, and the runner serves the
model end to end at a tiny size on the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.counts import dots3 as counts
from benchmark.reference import dots3 as ref
from benchmark.reference.common import Arith

CELL = "dots3-ep8-rag4k-saturated"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


@pytest.fixture
def dots3_tiny():
    """The configuration cut to a size the CPU holds
    (``data/dots3_tiny.json`` laid over the configuration file)."""
    cfg = _load("configs/dots3-note-prev-ep8-serve.json")
    over = _load("tests/data/dots3_tiny.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    return cfg


@pytest.fixture
def rag_tiny():
    tr = _load("traffic/rag4k-closed-2S.json")
    tr.update(ramp_s=0.5, pool_requests=32, trace_slice_s=0.5, job_requests=4,
              prompt_len={"dist": "lognormal", "median": 14, "sigma": 0.3,
                          "min": 9, "max": 24},
              max_new_tokens={"dist": "lognormal", "median": 5, "sigma": 0.5,
                              "min": 2, "max": 8})
    return tr


@pytest.mark.parametrize("shares", [8, 2, 1])
def test_shares_add_up_to_the_uncut_layer(dots3_tiny, shares):
    """The share test: the routed parts that all the shares give, with what
    every chip computes alike (the shared expert) counted once, equal the
    uncut 16-expert layer's FFN (float32: to rounding of another summation
    order, 1e-5 of the largest output)."""
    cfg = dots3_tiny
    E = cfg["n_routed_experts_published"]
    whole = ref._layer_weights(
        ref.init_weights(dict(cfg, experts_held=[0, E]), 7), 1)
    u = jax.random.normal(jax.random.key(0), (23, cfg["hidden_size"]))

    share = jax.jit(lambda u, lw, lo: ref.moe_parts(
        Arith("float32"), u, lw, cfg, (lo, None)))

    def parts(lo, hi):
        return share(u, {k: (v[lo:hi] if k.startswith("exp_") else v)
                         for k, v in whole.items()}, jnp.int32(lo))

    routed, shared, _ = parts(0, E)
    n = E // shares
    each = [parts(i * n, (i + 1) * n) for i in range(shares)]
    np.testing.assert_allclose(
        sum(p[0] for p in each) + each[0][1], routed + shared,
        atol=1e-5 * float(jnp.abs(routed + shared).max()))
    if shares > 1:      # a share alone is NOT the layer: the cut is real
        assert float(jnp.abs(each[0][0] - routed).max()) > 1e-3


def test_tolerant_gaps_resolve_ties_and_nothing_else():
    """Only a position whose gap is over the floor AND whose own routing
    has a margin under the tie is held against another resolution: each
    tie alone, then the ties a resolution opens in LATER layers (read from
    that resolution's own forward); the least gap is kept; a position that
    is explained is not looked at again."""
    inf = float("inf")
    margins = np.array([[1e-4, 0.5, 2e-4, 1e-4, inf, 3e-4],
                        [0.3, 0.4, 5e-4, 0.2, inf, 0.2],
                        [0.2, 0.3, 0.1, 0.3, 1e-5, 0.1]])
    gaps = np.array([0.08, 0.09, 0.07, 0.005, 0.0, 0.06])
    asked = []

    def again(flips):
        asked.append(flips.copy())
        theirs = margins.copy()
        if flips[0, 5]:            # token 5: the flip opens a tie below it
            theirs[2, 5] = 1e-4
        return np.array([0.001 if flips[0, 0] else 0.08, 0.0,
                         0.0 if flips[0, 2] and flips[1, 2] else 0.2, 0.0,
                         0.0, 0.002 if flips[0, 5] and flips[2, 5] else 0.3]
                        ), theirs

    log = []
    out = ref.tolerant_gaps(gaps, margins, again, tie=1e-3, floor=0.01,
                            log=log)
    np.testing.assert_allclose(out, [0.001, 0.09, 0.0, 0.005, 0.0, 0.002],
                               atol=1e-7)
    nodes = [{i: tuple(np.flatnonzero(f[:, i])) for i in range(6)
              if f[:, i].any()} for f in asked]
    assert nodes == [{0: (0,), 2: (0,), 5: (0,)}, {2: (1,), 5: (0, 2)},
                     {2: (0, 1)}]
    assert [(e["token"], tuple(e["layers"])) for e in log] == [
        (0, (0,)), (2, (0,)), (5, (0,)), (2, (1,)), (5, (0, 2)), (2, (0, 1))]
    # no tie at all: no second forward
    assert ref.tolerant_gaps(gaps, margins, None, tie=1e-6).tolist() \
        == gaps.astype(np.float32).tolist()
    # the forwards one call may add are bounded
    asked.clear()
    ref.tolerant_gaps(np.full(6, 0.5), np.full((3, 6), 1e-5),
                      lambda f: (asked.append(1) or np.full(6, 0.5),
                                 np.full((3, 6), 1e-5)))
    assert len(asked) == ref.TIE_FORWARDS


def test_a_tie_resolved_the_other_way_is_the_model_too(dots3_tiny):
    """``route`` with a flip takes the first expert left out in place of
    the last one chosen; tokens that are greedy under THAT resolution read
    a gap under the reference's own and none once the tie is allowed."""
    cfg = dots3_tiny
    w = ref.init_weights(cfg, 11)
    k, (lo, hi) = cfg["num_experts_per_tok"], cfg["experts_held"]
    u = jax.random.normal(jax.random.key(1), (64, cfg["hidden_size"]))
    lw = ref._layer_weights(w, 1)
    ar = Arith("float32")
    ids, _, margin = ref.route(ar, u, lw, cfg, (lo, hi))
    flip = jnp.arange(64) % 2 == 0
    ids_f, wt_f, _ = ref.route(ar, u, lw, cfg, (lo, hi), flip)
    s = jax.nn.sigmoid(u @ lw["router"].astype(jnp.float32)) + lw["router_b"]
    ninth = jnp.argsort(-s, -1)[:, k]
    np.testing.assert_array_equal(ids_f[:, :k - 1], ids[:, :k - 1])
    np.testing.assert_array_equal(ids_f[:, k - 1],
                                  jnp.where(flip, ninth, ids[:, k - 1]))
    np.testing.assert_allclose(wt_f.sum(-1), 1.0, rtol=1e-6)
    here = lambda e: (e >= lo) & (e < hi)                       # noqa: E731
    touches = np.asarray(here(ids[:, k - 1]) | here(ninth))
    assert np.isinf(np.asarray(margin)[~touches]).all() and touches.any()
    assert (np.asarray(margin)[touches] > 0).all()
    # the whole forward: flip every served position of every MoE layer
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 9).astype(np.int32)
    served = rng.integers(0, cfg["vocab_size"], 56).astype(np.int32)
    _, margins = ref._served(cfg, w, prompt, served, "float32")
    flips = np.isfinite(np.asarray(margins))
    other, _ = ref._served(cfg, w, prompt, served, "float32", flips)
    greedy = np.asarray(jnp.argmax(other, -1)).astype(np.int32)
    # (teacher-forced on ``served``: only the picks at each position differ)
    plain = np.asarray(ref._gaps(ref.served_logits(cfg, w, prompt, served),
                                 jnp.asarray(greedy)))
    assert plain.max() > 0.01                  # the choice moves the logits
    again = lambda f: ref._again(ref._served(                   # noqa: E731
        cfg, w, prompt, served, "float32", f), jnp.asarray(greedy))
    some = plain > 0.01
    tol = ref.tolerant_gaps(plain, margins, again, tie=np.inf, floor=0.01)
    one_tie = some & (flips.sum(0) == 1)
    assert one_tie.any() and (tol[one_tie] == 0).all()
    assert (tol <= plain + 1e-7).all()
    np.testing.assert_array_equal(
        ref.tolerant_gaps(plain, margins, again, tie=0.0), plain)


def test_counts_against_hand_counts():
    cfg = _load("configs/dots3-note-prev-ep8-serve.json")
    # ISSUE 27's own arithmetic, in millions
    assert round(counts.attention_parameters(cfg, "full_attention") / 1e6, 1) == 144.0
    assert round(counts.attention_parameters(cfg, "sliding_attention") / 1e6, 1) == 90.8
    assert counts.expert_parameters(cfg) == 3 * 5120 * 1536
    p = counts.parameters(cfg)
    assert p["experts"] == 4 * 32 * 3 * 5120 * 1536
    assert p["dense_ffn"] == 3 * 5120 * 13824
    assert round(sum(p.values()) / 1e9, 2) == 4.09
    assert counts.touched_experts(cfg, 0) == 0
    assert 31.9 < counts.touched_experts(cfg, 512) <= 32
    # one row, one step, context 4000: weights but the untouched experts,
    # 2048 latent rows and 4000 selector keys on each of 2 full layers, 513
    # window rows on each of 3 window layers
    s = counts.step(cfg, 1, 4, 2 * 2048, 2 * 4000)
    fixed = sum(v for k, v in p.items() if k not in ("experts", "embedding"))
    touched = 4 * 32 * (1 - (31 / 32) ** 1)
    assert s["bytes"] == pytest.approx(
        2 * (fixed + touched * counts.expert_parameters(cfg))
        + 2 * (2 * 2048 * 576 + 2 * 4000 * 128 + 3 * 513 * 1088))
    # (the norms' gains and the router's bias take part in no product)
    per_token = 2 * (fixed - 5 * 2 * 5120 - 5120 - 4 * 256)
    assert s["flops"] == pytest.approx(
        per_token + 2 * 4 * counts.expert_parameters(cfg)
        + 2 * 128 * (2 * 512 + 64) * 2 * 2048 + (2 * 64 * 128 + 128) * 2 * 4000
        + 2 * 64 * (2 * 1024 + 64) * 3 * 513, rel=2e-4)
    # a chunk reads a distinct column once for all its queries
    c = counts.chunk(cfg, 512, 2048, 2 * 512 * 2048, 2 * 512 * 3000)
    end = 3000 + 256
    assert c["bytes"] == pytest.approx(
        counts._weights_read(cfg, 2048)
        + 2 * (2 * 2048 * 576 + 2 * end * 128 + 3 * 1024 * 1088))


def test_runner_serves_the_model_end_to_end(dots3_tiny, rag_tiny):
    import time
    out = bench_run.run_cell(CELL, 2 ** 31 + 5, 2.0, True, config=dots3_tiny,
                             traffic=rag_tiny, check_device=False,
                             t_start=time.monotonic())
    assert out["correct"] is True and out["failed"] == 0
    c = {r["name"]: r for r in out["checks"]}
    # float32 on the CPU: the served tokens are the reference's greedy
    # tokens up to near-ties of the absorbed against the per-head order
    assert c["served_gap_rel_widest"]["value"] < 1e-4
    m = out["metrics"]
    # no device plane on the CPU: the counters' readers report
    assert {"steady_compiles.rag4k", "slot_occupancy_pct.rag4k",
            "moe_held_assignment_pct", "moe_tokens_per_held_expert",
            "sparse_selected_pct", "chunks_per_step.rag4k"} <= set(m)
    assert m["steady_compiles.rag4k"]["value"] == 0
    assert 10 < m["moe_held_assignment_pct"]["value"] < 45     # 4 of 16 held
    assert m["sparse_selected_pct"]["value"] < 80               # it binds

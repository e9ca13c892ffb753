"""The served weights lie where the slot programs contract over them
(``Generator.slot_execs``): the step and the chunk are compiled with the
parameters' layouts left to the compiler, a weight both want alike and
otherwise than it lies is relaid once, and every program compiled
afterwards takes the state as placed.

The CPU compiler asks for nothing but default layouts, so the mechanism
is engaged through ``conftest.weight_wishes``, a stub of the free compile
that asks for the transposed layout of chosen 2-D weights; without the
stub the same code is the pass-through."""
import functools
import os

import numpy as np
import pytest

import jax
from jax.experimental.layout import Format, Layout

import paddle_tpu as paddle
from paddle_tpu.framework.flags import (flags_restore, flags_snapshot,
                                        set_flags)
from paddle_tpu.profiler import ledger
from paddle_tpu.serving.slots import SlotLoop
from paddle_tpu.text.generation import Generator, agree_layouts
from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
from paddle_tpu.text.speculative import SpeculativeGenerator

V = 64
PROMPT = [3, 4, 5, 6, 7, 8, 9, 10, 11]
ROW, COL = Layout((0, 1), ()), Layout((1, 0), ())
COUNTERS = ("weights_relaid", "weights_relaid_mb", "weights_layout_disagreed")
Q0 = "encoder.layers.0.self_attn.q_proj.weight"


def _gpt(seed=21, hidden=32, layers=2):
    paddle.seed(seed)
    m = GPTModel(GPTConfig.tiny(vocab_size=V, hidden_size=hidden,
                                layers=layers, heads=2, seq=64))
    m.eval()
    return m


def _gen(m, site, cls=Generator, **kw):
    return cls(m, site=site, seq_buckets=(8, 16, 32), max_len=64, **kw)


@functools.lru_cache(maxsize=None)
def _want(steps=8):
    """What a Generator that never saw the mechanism generates."""
    g = _gen(_gpt(), "layouts:oracle")
    return np.asarray(g.generate(np.asarray([PROMPT], np.int32),
                                 max_new_tokens=steps).numpy())[0]


def _served(loop, steps=8):
    return np.asarray(loop.submit(PROMPT, steps).result(timeout=120)) \
        .reshape(-1)


def _layout(a):
    return a.format.layout


def _both_want(prog, name):
    return "q_proj" in name or name == "wte.weight"


def _chunk_wants_more(prog, name):
    return _both_want(prog, name) or (prog == "chunk" and "linear1" in name)


# -- the rule -----------------------------------------------------------------
@pytest.mark.parametrize("have,step,chunk,relaid,disagreed", [
    (ROW, COL, COL, {"w": COL}, []),        # agree and differ: relaid
    (ROW, ROW, COL, {}, ["w"]),             # disagree: both take it as it is
    (ROW, COL, ROW, {}, ["w"]),
    (ROW, ROW, ROW, {}, []),                # agree on what it has: untouched
    (COL, COL, COL, {}, []),                # ... also where that is no default
    (ROW, None, COL, {"w": COL}, []),       # a program that does not read it
    (ROW, None, None, {}, []),              # has no wish
], ids=["agree-and-differ", "chunk-alone", "step-alone", "agree-on-default",
        "agree-on-held", "one-reader", "no-reader"])
def test_agreement_rule(have, step, chunk, relaid, disagreed):
    got = agree_layouts({"w": have, "b": ROW}, {"w": step, "b": ROW},
                        {"w": chunk, "b": ROW})
    assert got == (relaid, disagreed)


# -- engaged through the stub -------------------------------------------------
def test_relaid_slot_loop_emits_generates_tokens(weight_wishes):
    weight_wishes(_both_want)
    m = _gpt()
    gen = _gen(m, "layouts:relaid")
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        assert gen.weights_layout["weights_relaid"] == 3   # 2 q_proj + wte
        assert gen.weights_layout["weights_layout_disagreed"] == 0
        # the snapshot AND the bound layer hold the relaid arrays
        assert _layout(gen._params[Q0]) == COL
        assert m.encoder.layers[0].self_attn.q_proj.weight._value \
            is gen._params[Q0]
        assert _layout(gen._params["encoder.layers.0.linear1.weight"]) == ROW
        # both free programs are the final ones: two compiles, no third
        # (and the loop's own mover of a logits row, which reads no weight)
        kinds = [e["kind"] for e in ledger.compile_events("layouts:relaid")]
        assert kinds == ["generate_step", "generate_chunk", "logits_put_row"]
        np.testing.assert_array_equal(_served(loop), _want())
    finally:
        loop.close()


def test_disagreement_pins_both_and_recompiles_the_asker(weight_wishes):
    weight_wishes(_chunk_wants_more)
    gen = _gen(_gpt(), "layouts:disagree")
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        assert gen.weights_layout == {
            "weights_relaid": 3, "weights_layout_disagreed": 2,
            "weights_relaid_mb": gen.weights_layout["weights_relaid_mb"]}
        # linear1 stays as it lay; the step's free program is final, the
        # chunk is compiled again for the weights as they lie
        assert _layout(gen._params["encoder.layers.0.linear1.weight"]) == ROW
        evs = ledger.compile_events("layouts:disagree")
        assert [e["kind"] for e in evs] == [
            "generate_step", "generate_chunk", "generate_chunk",
            "logits_put_row"]
        assert "auto" in evs[1]["key"] and "auto" not in evs[2]["key"]
        chunk_in = loop._chunk.input_formats[0][0]
        assert chunk_in["encoder.layers.0.linear1.weight"].layout == ROW
        assert chunk_in[Q0].layout == COL
        np.testing.assert_array_equal(_served(loop), _want())
    finally:
        loop.close()


def test_refresh_state_keeps_the_formats(weight_wishes):
    weight_wishes(_both_want)
    m = _gpt()
    gen = _gen(m, "layouts:refresh")
    gen.slot_execs(4, 8, 64)
    relaid = gen._params[Q0]
    gen.refresh_state()                     # nothing new: nothing moves
    assert gen._params[Q0] is relaid
    # new weights arrive in the default layout (a checkpoint load)
    fresh = _gpt(seed=22)
    m.set_state_dict(fresh.state_dict())
    assert _layout(m.encoder.layers[0].self_attn.q_proj.weight._value) == ROW
    gen.refresh_state()
    assert _layout(gen._params[Q0]) == COL
    assert m.encoder.layers[0].self_attn.q_proj.weight._value \
        is gen._params[Q0]
    np.testing.assert_array_equal(
        np.asarray(gen._params[Q0]),
        np.asarray(fresh.encoder.layers[0].self_attn.q_proj.weight._value))
    # and the programs compiled before take the new snapshot
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        want = np.asarray(_gen(fresh, "layouts:refresh_oracle").generate(
            np.asarray([PROMPT], np.int32), max_new_tokens=8).numpy())[0]
        np.testing.assert_array_equal(_served(loop), want)
    finally:
        loop.close()


def test_programs_compiled_afterwards_take_the_placed_state(weight_wishes):
    weight_wishes(_both_want)
    gen = _gen(_gpt(), "layouts:after")
    gen.slot_execs(4, 8, 64)
    pre = gen.prefill_exec(1, 16, 32)
    dec = gen.decode_exec(1, 32, 8)
    for ex in (pre, dec):
        fmts = ex.input_formats[0][0]
        assert fmts[Q0].layout == COL and fmts["wte.weight"].layout == COL
        assert fmts["encoder.layers.0.linear1.weight"].layout == ROW
    got = np.asarray(gen.generate(np.asarray([PROMPT], np.int32),
                                  max_new_tokens=8).numpy())[0]
    np.testing.assert_array_equal(got, _want())
    # beam search rides the same state
    paths, _ = gen.generate(np.asarray([PROMPT], np.int32),
                            max_new_tokens=4, beam_size=2)
    assert np.asarray(paths.numpy()).shape == (1, 2, 4)


def test_speculative_pair_relays_target_and_draft(weight_wishes):
    weight_wishes(_both_want)
    m, d = _gpt(), _gpt(seed=101, hidden=16, layers=1)
    gen = _gen(m, "layouts:spec", cls=SpeculativeGenerator, draft=d, gamma=3)
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        # 2 q_proj + wte of the target, 1 q_proj + wte of the draft
        assert gen.weights_layout["weights_relaid"] == 5
        assert _layout(gen._d_params[Q0]) == COL
        assert d.wte.weight._value is gen._d_params["wte.weight"]
        np.testing.assert_array_equal(_served(loop), _want())
        # the scanned pair, compiled afterwards, takes both placed states
        got = np.asarray(gen.generate(np.asarray([PROMPT], np.int32),
                                      max_new_tokens=8).numpy())[0]
        np.testing.assert_array_equal(got, _want())
    finally:
        loop.close()


def test_another_generator_on_the_relaid_layer(weight_wishes):
    weight_wishes(_both_want)
    m = _gpt()
    before = _gen(m, "layouts:before")      # snapshot in default layouts
    _gen(m, "layouts:relayer").slot_execs(4, 8, 64)
    # one built afterwards takes the arrays as they now lie
    after = _gen(m, "layouts:later")
    assert after._formats[0, Q0].layout == COL
    got = np.asarray(after.generate(np.asarray([PROMPT], np.int32),
                                    max_new_tokens=8).numpy())[0]
    np.testing.assert_array_equal(got, _want())
    # one built before keeps its own layouts across a refresh
    before.refresh_state()
    assert before._formats == {} and _layout(before._params[Q0]) == ROW
    got = np.asarray(before.generate(np.asarray([PROMPT], np.int32),
                                     max_new_tokens=8).numpy())[0]
    np.testing.assert_array_equal(got, _want())


def test_programs_compiled_before_settle_the_layouts(weight_wishes):
    weight_wishes(_both_want)
    gen = _gen(_gpt(), "layouts:settled")
    gen.prefill_exec(1, 16, 32)             # compiled for default layouts
    step, chunk = gen.slot_execs(4, 8, 64)
    assert gen.weights_layout["weights_relaid"] == 0 and gen._formats == {}
    assert step.input_formats[0][0][Q0].layout == ROW
    assert all("auto" not in e["key"]
               for e in ledger.compile_events("layouts:settled"))


def test_counters_in_stats_and_ledger_events(weight_wishes):
    weight_wishes(_chunk_wants_more)
    gen = _gen(_gpt(), "layouts:counters")
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        stats = loop.stats()
        assert {k: stats[k] for k in COUNTERS} == gen.weights_layout
        assert stats["weights_relaid"] == 3
        assert stats["weights_relaid_mb"] == pytest.approx(
            (2 * 32 * 32 + V * 32) * 4 / 1e6, abs=1e-3)
        evs = [e for e in ledger.compile_events("layouts:counters")
               if e["kind"].startswith("generate_")]    # the two that read weights
        assert len(evs) == 3
        for e in evs:
            assert {k: e[k] for k in COUNTERS} == gen.weights_layout
        loop.reset_stats()                  # facts of the programs stay
        assert loop.stats()["weights_relaid"] == 3
    finally:
        loop.close()


# -- the pass-through, and who never takes part -------------------------------
def test_cpu_pass_through_two_compiles_nothing_relaid():
    m = _gpt()
    held = m.wte.weight._value
    gen = _gen(m, "layouts:pass")
    loop = SlotLoop(gen, slots=4, cache_len=64, chunk=8)
    try:
        assert gen.weights_layout == dict.fromkeys(COUNTERS, 0)
        assert gen._formats == {} and gen._params["wte.weight"] is held
        evs = ledger.compile_events("layouts:pass")
        assert [e["kind"] for e in evs] == ["generate_step", "generate_chunk",
                                            "logits_put_row"]
        # the free programs serve under the plain keys too
        assert gen.step_exec(4, 64) is loop._step
        assert gen.chunk_exec(4, 8, 64) is loop._chunk
        assert len(ledger.compile_events("layouts:pass")) == 3
        np.testing.assert_array_equal(_served(loop), _want())
    finally:
        loop.close()


def test_without_slot_programs_default_layouts_plain_avals(weight_wishes):
    weight_wishes(_both_want)               # never asked: never answered
    gen = _gen(_gpt(), "layouts:never")
    gen.generate(np.asarray([PROMPT], np.int32), max_new_tokens=4)
    assert gen._formats == {} and gen.weights_layout["weights_relaid"] == 0
    assert all(a.sharding is None for tree in gen._state_avals()
               for a in tree.values())
    assert "weight_formats" not in repr(gen._program_identity())


def test_under_a_mesh_default_layouts_and_no_slot_programs():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    gen = Generator(_gpt(), site="layouts:mesh", seq_buckets=(8, 16),
                    max_len=16, mesh=mesh)
    assert gen._formats is None             # nothing is ever looked at
    for tree in gen._state_avals():
        assert not any(isinstance(a.sharding, Format) for a in tree.values())
    with pytest.raises(Exception, match="unsharded"):
        gen.slot_execs(2, 8, 16)


# -- the persistent cache's warm path -----------------------------------------
def test_warm_start_reads_the_formats_off_the_loaded_programs(weight_wishes,
                                                              tmp_path):
    weight_wishes(_chunk_wants_more)
    snap = flags_snapshot()
    d = str(tmp_path / "exec_cache")
    os.makedirs(d)
    set_flags({"FLAGS_executable_cache": "readwrite",
               "FLAGS_executable_cache_dir": d})
    try:
        cold = _gen(_gpt(), "layouts:cold")
        cold.slot_execs(4, 8, 64)
        cold.put_logits_row_exec(4)
        kinds = [e["kind"] for e in ledger.compile_events("layouts:cold")]
        assert kinds == ["generate_step", "generate_chunk", "generate_chunk",
                         "logits_put_row"]
        # a new process: the same architecture, weights in default layouts
        warm = _gen(_gpt(), "layouts:warm")
        loop = SlotLoop(warm, slots=4, cache_len=64, chunk=8)
        try:
            kinds = [e["kind"] for e in ledger.compile_events("layouts:warm")]
            assert kinds == ["cache_load"] * 4          # no compile at all
            assert warm._formats == cold._formats and warm._formats
            assert warm.weights_layout == cold.weights_layout
            mark = len(ledger.compile_events("layouts:warm"))
            np.testing.assert_array_equal(_served(loop), _want())
            assert len(ledger.compile_events("layouts:warm")) == mark
        finally:
            loop.close()
    finally:
        flags_restore(snap)


# -- the benchmark's reader ---------------------------------------------------
def test_benchmark_reader_reads_the_counter_or_nothing():
    import json
    from benchmark import run
    from benchmark.layer_metrics import weights_relaid_mb
    stats = {"steps": 3, "slots": 4, "weights_relaid_mb": 901.379}
    assert weights_relaid_mb.compute(
        {"counters": {"slot_loop": stats}}) == 901.379
    # the parent's loop keeps no such counter: nothing is read, nothing raises
    del stats["weights_relaid_mb"]
    ctx = {"counters": {"slot_loop": stats}}
    assert weights_relaid_mb.compute(ctx) is None
    assert weights_relaid_mb.compute({}) is None
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {m["name"]: m["workloads"] for m in bench["per_layer"]
             if m["name"].startswith("weights_relaid_mb")}
    assert cells == {
        "weights_relaid_mb.saturated": ["gpt2-xl-batch-saturated"],
        "weights_relaid_mb.open": ["gpt2-xl-chat-open"]}
    for cell in ("gpt2-xl-batch-saturated", "gpt2-xl-chat-open"):
        per_layer = run.load_cell(cell)["per_layer"]
        got = run.layer_metrics({"per_layer": [
            m for m in per_layer if m["name"].startswith("weights_relaid")]},
            {"counters": {"slot_loop": {"weights_relaid_mb": 901.379}}})
        assert [v["value"] for v in got.values()] == [901.379]

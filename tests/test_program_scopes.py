"""Device time by program and by named scope (PERF.md section 3): the scope
tables the program hands out (``profiler.ledger.program_scopes``), that a
scope is metadata and nothing else, and the reduction that lays a capture's
ops under programs and buckets (``benchmark/layer_metrics/
_program_scopes.py``) on the recorded TPU capture.  The five served
families at their tiny sizes and a tiny ``TrainStep``, on the CPU.
"""
import contextlib
import gc
import importlib
import json
import os
import re
import sys
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce                             # noqa: E402
from benchmark.layer_metrics import _program_scopes as ps     # noqa: E402
from paddle_tpu.profiler import ledger                        # noqa: E402
from paddle_tpu.text.generation import Generator              # noqa: E402

CAPTURE = os.path.join(ROOT, "benchmark", "tests", "data",
                       "train_two_steps.xplane.pb.gz")
FAMILIES = ("gpt", "dots3", "lfm2", "kimi_k2", "glm_moe_dsa")
TINY = {"dots3": ("dots3-note-prev-ep8-serve", "dots3_tiny"),
        "lfm2": ("lfm2-8b-a1b-pp2-serve", "lfm2_tiny"),
        "kimi_k2": ("kimi-k2.5-ep32-serve", "kimi_tiny"),
        "glm_moe_dsa": ("glm-5-ep16-serve", "glm5_tiny")}
SLOTS, CHUNK, COLUMNS = 3, 4, 64
# an instruction line of a compiled program's text: its name and its opcode
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s+\S+\s+([\w\-]+)\(",
                   re.M)


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


def _model(family):
    """The family's model at its tiny size, weights as the constructors
    made them."""
    if family == "gpt":
        from paddle_tpu.text.models.gpt import GPTConfig, GPTModel
        return GPTModel(GPTConfig.tiny())
    config, tiny = TINY[family]
    cfg = _load(f"configs/{config}.json")
    over = _load(f"tests/data/{tiny}.json")["over"]
    cfg["serve"].update(over.pop("serve"))
    cfg.update(over)
    models = importlib.import_module(f"benchmark.models.{family}")
    ref = importlib.import_module(f"benchmark.reference.{family}")
    return models.build(cfg, models.to_program(ref.init_weights(cfg, 5)))


def _train_step():
    """A tiny BERT pretraining step, compute in bfloat16 (so that the
    cast of the parameters is in the program), and its feed."""
    import paddle_tpu as paddle
    from paddle_tpu.parallel import TrainStep
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining
    cfg = BertConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=16, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(model, opt, mesh=make_mesh({"dp": 1}, jax.devices()[:1]),
                     compute_dtype=jnp.bfloat16, seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, (2, 16)).astype(np.int32)
    positions = np.tile(np.arange(3, dtype=np.int32), (2, 1))
    labels = rng.integers(0, 96, (2, 3)).astype(np.int32)
    return step, (ids, None, None, labels, None, positions)


def _buckets(table, component_bucket):
    return {name: ps.bucket_of(entry["scope"], component_bucket)
            for name, entry in table.items()}


def _products(text):
    """Names of the instructions of ``text`` that are a matrix product or
    a convolution."""
    return [name for name, op in _LINE.findall(text)
            if op in ("dot", "convolution")]


@pytest.fixture()
def fresh_ledger():
    ledger.clear()
    yield
    ledger.clear()


# -- (a) the tables -----------------------------------------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_served_programs_name_their_products_and_row_moves(family,
                                                           fresh_ledger):
    gen = Generator(_model(family), max_len=COLUMNS, seq_buckets=[COLUMNS])
    execs = {"jit_step": gen.step_exec(SLOTS, COLUMNS),
             "jit_chunk": gen.chunk_exec(SLOTS, CHUNK, COLUMNS)}
    tables = ledger.program_scopes()
    component_bucket = ps.load_buckets()
    assert set(tables) == set(execs)
    for program, ex in execs.items():
        text = ex.as_text()
        table = tables[program]
        # every instruction of the text is in the table, product or not
        assert {n for n, _ in _LINE.findall(text)} <= set(table)
        buckets = _buckets(table, component_bucket)
        products = _products(text)
        assert products
        # the CPU compiler rewrites some products and gives the new
        # instruction no metadata at all (no op_name: nothing to read);
        # every product that says where it came from is in a bucket
        named = [n for n in products
                 if re.search(rf"%?{re.escape(n)} = [^\n]*op_name=", text)]
        assert named and len(named) >= len(products) // 2
        assert [n for n in named if buckets[n] == ps.UNSCOPED] == []
    moves = {e["scope"] for e in tables["jit_chunk"].values()}
    # a chunk that runs on a cut-out row names the row's way out and back;
    # one that writes its block into the planes in place (ISSUE 46: the
    # GPT family) has neither, only the block's write
    sliced = gen.chunk_row() == "sliced"
    assert any(s.endswith("cache_write/row_slice") for s in moves) == sliced
    assert any(s.endswith("cache_write/row_splice") for s in moves) == sliced
    assert any(s.endswith("/cache_write") for s in moves)
    chunk = _buckets(tables["jit_chunk"], component_bucket)
    assert "cache_write" in chunk.values()
    assert all(chunk[n] == "cache_write"
               for n, e in tables["jit_chunk"].items()
               if "row_slice" in e["scope"] or "row_splice" in e["scope"])
    assert not any(e.get("backward") for t in tables.values()
                   for e in t.values())
    assert "head/sample" in {e["scope"] for e in tables["jit_step"].values()}


def test_train_step_marks_backward_and_names_its_own_parts(fresh_ledger):
    step, feed = _train_step()
    step(feed)
    tables = ledger.program_scopes()
    assert list(tables) == ["jit_step"]
    table, component_bucket = tables["jit_step"], ps.load_buckets()
    counts = ps.count_buckets(table, component_bucket)
    assert {"attention", "mlp", "embed", "head", "loss", "optimizer",
            "cast_params"} <= set(counts)
    backward = {ps.bucket_of(e["scope"], component_bucket)
                for e in table.values() if e.get("backward")}
    assert {"attention", "mlp"} <= backward
    assert not any(e.get("backward") for e in table.values()
                   if ps.bucket_of(e["scope"], component_bucket)
                   == "optimizer")
    text = step.aot_compile(feed).as_text()
    named = [n for n in _products(text)
             if re.search(rf"%?{re.escape(n)} = [^\n]*op_name=", text)]
    buckets = _buckets(table, component_bucket)
    assert named and [n for n in named if buckets[n] == ps.UNSCOPED] == []


@pytest.mark.parametrize("op_name, scope, backward", [
    ("jit(step)/jit(main)/transpose(jvp(attention))/dot_general",
     "attention", True),
    ("jit(chunk)/attention/jit(_sdpa_packed_fn)", "attention", False),
    ("jit(f)/jit(main)/attention/while/body/cache_write/dynamic_slice",
     "attention/while/body/cache_write", False),
    ("jit(f)/jit(main)/transpose(jvp(cache_write/row_slice))/mul",
     "cache_write/row_slice", True),
    ("jit(f)/jit(main)/jvp(jit(inner))/mul", "", False),
    ("jit(chunk)/dynamic_slice", "", False),
    ("", "", False),
])
def test_scope_of_an_op_name(op_name, scope, backward):
    got = ledger.scope_of(op_name)
    assert got["scope"] == scope and bool(got.get("backward")) is backward


def test_parse_scopes_lists_every_instruction():
    text = "\n".join([
        "HloModule jit_chunk, is_scheduled=true",
        "%fused (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name='
        '"jit(chunk)/mlp/neg" stack_frame_id=3}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="a"}',
        "  %copy.2 = f32[4]{0} copy(%a)",
        '  ROOT %fusion.7 = f32[4]{0} fusion(%copy.2), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(chunk)/attention/'
        'cache_write/dynamic_update_slice"}',
        "}"])
    module, table = ledger.parse_scopes(text)
    assert module == "jit_chunk"
    # copy.2 has no op_name: the compiler made it, for fusion.7
    assert table == {"p": {"scope": "mlp", "inherited": True},
                     "neg.1": {"scope": "mlp"},
                     "a": {"scope": ""},
                     "copy.2": {"scope": "attention/cache_write",
                                "inherited": True},
                     "fusion.7": {"scope": "attention/cache_write"}}
    cb = ps.load_buckets()
    assert ps.bucket_of("attention/cache_write", cb) == "cache_write"
    assert ps.bucket_of("attention/while/body", cb) == "attention"
    assert ps.bucket_of("attention/latent_attention/per_head", cb) \
        == "attention"
    assert ps.bucket_of("experts/router", cb) == "experts"
    assert ps.bucket_of("experts/mlp", cb) == "mlp"
    assert ps.bucket_of("head/sample", cb) == "head"
    assert ps.bucket_of("while/body", cb) == ps.UNSCOPED


@pytest.mark.parametrize("program", ["jit_step", "jit_chunk"])
def test_every_branch_of_the_selectors_search_is_under_its_scope(
        program, fresh_ledger):
    """The search over the live span is one conditional with a branch for
    "nothing to decide" and one for each width: the instructions of every
    branch lie under ``selector/select`` (those of the score loop under
    ``selector/score``), so that the readers of the component ``selector``
    and of the ``attention`` bucket see them whichever branch runs."""
    model = _model("glm_moe_dsa")
    gen = Generator(model, max_len=COLUMNS, seq_buckets=[COLUMNS])
    if program == "jit_step":
        gen.step_exec(SLOTS, COLUMNS)
    else:
        gen.chunk_exec(SLOTS, CHUNK, COLUMNS)
    table = ledger.program_scopes()[program]
    widths = gen.selector_widths(COLUMNS)
    assert widths == [12, 24, 48, 64]
    scopes = {e["scope"] for e in table.values()}
    select = "attention/latent_attention/selector/select"
    for branch in range(len(widths) + 1):
        assert any(s.startswith(f"{select}/cond/branch_{branch}_fun")
                   for s in scopes), branch
    assert any(s.startswith("attention/latent_attention/selector/score/while")
               for s in scopes)
    # no branch of that conditional lies anywhere else, and all of it is
    # in the attention bucket
    branches = {n: e["scope"] for n, e in table.items()
                if re.search(r"cond/branch_\d_fun", e["scope"])
                and "experts" not in e["scope"]}
    assert branches and all(select in s for s in branches.values())
    buckets = _buckets(table, ps.load_buckets())
    assert {buckets[n] for n in branches} == {"attention"}


def _layer_text(attn, cache_len=COLUMNS, width=CHUNK):
    """One latent-attention layer's cached call, lowered alone."""
    from paddle_tpu.framework.tensor import Tensor, unwrap
    planes = [unwrap(p) for p in attn.gen_ring_cache(SLOTS, cache_len)]

    def call(x, planes, pos, start):
        out, cache = attn.forward_cached(
            x, type(attn.gen_ring_cache(1, cache_len))(
                *(Tensor(p) for p in planes)), pos, start)
        return out, [unwrap(p) for p in cache]
    return jax.jit(call).lower(
        jnp.zeros((SLOTS, width, attn.hidden)), planes, jnp.int32(0),
        jnp.zeros((SLOTS,), jnp.int32)).as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["no_selector", "window", "short_plane",
                                  "selector"])
def test_only_a_searching_layer_lowers_a_conditional(kind):
    """A full layer without a selector (``index_topk`` 0), a window layer,
    and a selecting layer whose plane holds no more than ``index_topk``
    columns lower as they did: no conditional, and no ``selector/select``
    scope; the layer that searches lowers exactly one."""
    from paddle_tpu.nn.layer.latent_attention import LatentAttention
    kw = dict(index_heads=2, index_dim=16, cache_block=4, attn_block=8)
    attn = LatentAttention(
        32, 4, 8, 8, 12, 16, 12, 100.0,
        window=16 if kind == "window" else None,
        index_topk={"no_selector": 0, "window": 0, "short_plane": COLUMNS,
                    "selector": 6}[kind], **kw)
    text = _layer_text(attn)
    searches = kind == "selector"
    assert text.count("stablehlo.case") == (1 if searches else 0)
    assert ("selector/select" in text) == searches
    assert ("selector/score" in text) == searches
    assert ("/selector/" in text) == (kind in ("selector", "short_plane"))
    assert (attn.search_widths(COLUMNS) is None) \
        == (kind in ("no_selector", "window"))
    if kind == "short_plane":
        assert attn.search_widths(COLUMNS) == ()


# -- (b) a scope is metadata and nothing else ---------------------------------
def _lowered_texts(family):
    """The StableHLO text (no locations) of the family's step and chunk, or
    of the training step."""
    if family == "train":
        step, feed = _train_step()
        return [step.aot_lower(feed).as_text()]
    gen = Generator(_model(family), max_len=COLUMNS, seq_buckets=[COLUMNS])
    out = []
    for prog in (gen._step_program(SLOTS, COLUMNS),
                 gen._chunk_program(SLOTS, CHUNK, COLUMNS)):
        _, _, fn, avals, _, donate = prog
        out.append(jax.jit(fn, donate_argnums=donate).lower(
            *gen._state_avals(), *avals).as_text())
    return out


@pytest.mark.parametrize("family", FAMILIES + ("train",))
def test_programs_lower_alike_with_and_without_scopes(family, monkeypatch):
    named = _lowered_texts(family)
    assert all("loc(" not in t for t in named)
    entered = []

    def no_scope(name):
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    plain = _lowered_texts(family)
    assert entered and plain == named


# -- (c) the reduction, on the recorded TPU capture ----------------------------
@pytest.fixture(scope="module")
def capture():
    return trace_reduce.load(CAPTURE)


def test_self_times_partition_the_program(capture):
    (modules, ops), = ps._device_lines(capture)
    runs, _stray = ps.lay(modules, ops)
    steps = [k for k, m in enumerate(modules) if m[2].startswith("jit_step(")]
    assert len(steps) == 2
    for k in steps:
        a, nb, _ = modules[k]
        inside = [(o[0], -o[1]) for o in ops if a <= o[0] and o[1] >= nb]
        union = sum(y - x for x, y in trace_reduce.union(inside))
        selfs = runs[k]["ops"]
        assert sum(selfs.values()) == pytest.approx(union, rel=1e-3)
        assert min(selfs.values()) >= 0.0
        # no op is counted twice: where ops nest (a while holds its body)
        # their plain durations add up to more than the time that passed
        assert sum(selfs.values()) <= sum(y - x for x, y in inside) + 1e-6
    assert runs[steps[0]]["first"] == runs[steps[1]]["first"]
    assert runs[steps[0]]["last"] == runs[steps[1]]["last"]


def test_self_times_of_ops_that_nest_and_overlap():
    #   0....10 outer; 2..5 child; 3..4 grandchild; 8..12 overlaps the end
    ops = [(0.0, -10.0, "outer"), (2.0, -5.0, "child"), (3.0, -4.0, "grand"),
           (8.0, -12.0, "late"), (20.0, -21.0, "alone"), (40.0, -41.0, "out")]
    runs, stray = ps.lay([(0.0, -30.0, "jit_f(1)")], ops)
    assert runs == [{"ops": {"outer": 5.0, "child": 2.0, "grand": 1.0,
                             "late": 4.0, "alone": 1.0},
                     "first": "outer", "last": "alone"}]
    assert stray == {"out": 1.0}
    # an op that ends after its run is under no run
    runs, stray = ps.lay([(0.0, -9.0, "jit_f(1)")], ops[:1])
    assert runs[0]["ops"] == {} and stray == {"outer": 10.0}


def test_runs_cut_by_the_edge_of_the_slice_are_told_by_their_ops():
    def run(names):
        return {"ops": dict.fromkeys(names, 1.0), "first": names[0],
                "last": names[-1]} if names else \
            {"ops": {}, "first": None, "last": None}
    whole = ["first", "mid", "last"]
    modules = [(10.0 * k, -(10.0 * k + 5), "jit_step(7)") for k in range(5)]
    runs = [run(whole) for _ in modules]
    assert ps._cut_runs(modules, runs) == set()
    runs[0] = run(whole[1:])                    # began before the capture
    runs[-1] = run(whole[:2])                   # ended after it
    assert ps._cut_runs(modules, runs) == {0, 4}
    runs[-1] = run([])                          # an event and not one op
    assert ps._cut_runs(modules, runs) == {0, 4}
    # a program with fewer than three runs has no majority to differ from
    assert ps._cut_runs(modules[:2], [run(whole[1:]), run(whole)]) == set()
    assert ps._cut_runs([], []) == set()


def test_buckets_and_unscoped_add_up_to_the_program(capture):
    (modules, ops), = ps._device_lines(capture)
    a, nb, _ = modules[0]
    names = sorted({ps.instruction_name(op[2]) for op in ops
                    if a <= op[0] and op[1] >= nb})
    picked = names[:: len(names) // 3][:3]
    table = {n: {"scope": ""} for n in names}
    table[picked[0]] = {"scope": "attention/while/body"}
    table[picked[1]] = {"scope": "attention/cache_write", "backward": True}
    table[picked[2]] = {"scope": "optimizer"}
    out = ps.reduce_profile(capture, {"jit_step": table}, ps.load_buckets())
    p = out["programs"]["jit_step"]
    assert p["runs"] == 2 and p["has_table"]
    assert set(p["buckets"]) == {"attention", "cache_write", "optimizer"}
    assert all(v > 0 for v in p["buckets"].values())
    assert p[ps.UNKNOWN] == 0.0
    assert sum(p["buckets"].values()) + p[ps.UNSCOPED] \
        == pytest.approx(p["ops_ms"], rel=1e-9)
    # the gaps between the ops of a run are all that the rows leave out
    assert p["ops_ms"] == pytest.approx(p["mean_ms"], rel=1e-2)
    assert p["ops_ms"] <= p["mean_ms"]
    assert p["backward"] == {"cache_write": p["buckets"]["cache_write"]}
    assert out["scoped_s"] == pytest.approx(
        1e-3 * 2 * sum(p["buckets"].values()))
    assert out["busy_s"] == pytest.approx(1e-3 * 2 * p["ops_ms"])
    # ops after the last whole run (the capture ended inside the next)
    assert out["no_module_s"] > 0
    assert len(p["top_ops"]) == ps.TOP_OPS
    # a table without one of the names: that op's time is reported apart
    del table[picked[2]]
    out = ps.reduce_profile(capture, {"jit_step": table}, ps.load_buckets())
    assert out["programs"]["jit_step"][ps.UNKNOWN] > 0
    # and a program without a table is unscoped, whole
    out = ps.reduce_profile(capture, {}, ps.load_buckets())
    q = out["programs"]["jit_step"]
    assert not q["has_table"] and q["buckets"] == {} \
        and q[ps.UNSCOPED] == pytest.approx(q["ops_ms"])
    assert out["scoped_s"] == 0.0


def test_readers_find_nothing_without_a_table_or_a_capture(monkeypatch):
    ctx = {"trace": None, "cell": {"bench_dir": os.path.join(ROOT,
                                                             "benchmark")},
           "programs": {"step": "jit_step"}}
    assert ps.bucket_ms(ctx, "step", "attention") is None
    assert ps.scoped_pct(ctx) is None
    # a checkout whose ledger hands out no tables (the parent of this PR)
    monkeypatch.delattr(ledger, "program_scopes")
    assert ps.program_scopes() is None
    ctx = dict(ctx, trace={"programs": {}})
    assert ps.bucket_ms(ctx, "step", "attention") is None


# -- (d) nothing happens until the table is asked for --------------------------
def test_texts_are_read_on_demand_and_hold_no_program_alive(fresh_ledger,
                                                            monkeypatch):
    parsed = []
    real = ledger.parse_scopes
    monkeypatch.setattr(ledger, "parse_scopes",
                        lambda text: parsed.append(1) or real(text))
    gen = Generator(_model("gpt"), max_len=COLUMNS, seq_buckets=[COLUMNS])
    gen.step_exec(SLOTS, COLUMNS)
    kept = weakref.ref(gen)
    assert parsed == [] and len(ledger._texts) == 1
    assert ledger._texts[0]["table"] is None
    assert list(ledger.program_scopes()) == ["jit_step"] and parsed == [1]
    ledger.program_scopes()
    assert parsed == [1]                        # kept beside the event
    gen.chunk_exec(SLOTS, CHUNK, COLUMNS)
    del gen
    gc.collect()
    assert kept() is None
    # the chunk's text was never asked for, and now cannot be: its owner
    # is gone; the step's table stays
    assert list(ledger.program_scopes()) == ["jit_step"] and parsed == [1]


def test_profiler_leaves_the_tables_beside_its_capture(fresh_ledger, capsys):
    """A capture can be read by program and scope after the process is
    gone: ``Profiler`` writes ``program_scopes.json`` into its trace
    directory when a window ends, and the module's command line reads the
    two files (no device plane in a CPU capture: an empty table)."""
    from paddle_tpu import profiler
    gen = Generator(_model("gpt"), max_len=COLUMNS, seq_buckets=[COLUMNS])
    with profiler.Profiler() as prof:
        gen.step_exec(SLOTS, COLUMNS)
    path = os.path.join(prof.profiler_result_dir, "program_scopes.json")
    with open(path) as f:
        assert json.load(f) == ledger.program_scopes()
    assert "jit_step" in ledger.program_scopes()
    assert ps.main([prof.profiler_result_dir]) == 0
    assert "device seconds by scope, slice:" in capsys.readouterr().out


def test_an_untraced_run_asks_for_no_table(monkeypatch):
    """``--trace 0`` calls nothing this PR adds."""
    import time
    from benchmark import run as bench_run
    cfg = _load("configs/bert-large-pretrain.json")
    cfg.update(vocab_size=128, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=2, intermediate_size=128,
               max_position_embeddings=64, dtype="float32")
    cfg["train"].update(batch=4, seq=32, masked_per_seq=5)
    cfg["reference"]["rows_per_block"] = 2

    def refuse():
        raise AssertionError("program_scopes() called in an untraced run")

    monkeypatch.setattr(ledger, "program_scopes", refuse)
    monkeypatch.setattr(ledger, "parse_scopes", lambda text: refuse())
    # (JAX's persistent cache stays as the other tests of this process
    # have it: switched on here, it would serve them loaded executables)
    monkeypatch.setattr(bench_run, "enable_compile_cache", lambda: None)
    out = bench_run.run_cell("bert-large-pretrain-s512", 2 ** 31 + 11, 0.5,
                             False, config=cfg, check_device=False,
                             t_start=time.monotonic())
    assert out["correct"] is True
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}

"""tools/kv_layout_check.py reads the compiled program's text: its parser
on two recorded excerpts (the lane-major planes of commit 43f841c and the
packed planes), without libtpu — the tool itself compiles for a described
v5e and is run by hand."""
import importlib.util
import os

import pytest

_TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "kv_layout_check.py")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("kv_layout_check", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hlo(shape, layout, aligned, body=""):
    dims = ",".join(str(d) for d in shape)
    plane = f"bf16[{dims}]{{{layout}:T(8,128)(2,1)}}"
    return f"""HloModule jit_step, is_scheduled=true, input_output_alias={{ {{0}}: (1, {{}}, may-alias), {{1}}: (2, {{}}, may-alias) }}

%fused_computation.1 (p: bf16[4]) -> bf16[4] {{
  %copy.9 = {plane} copy(%nothing)
}}

ENTRY %main.1 (w: bf16[8,8], c0: {plane}, c1: {plane}) -> ({plane}, {plane}) {{
  %w.1 = bf16[8,8]{{1,0:T(8,128)(2,1)}} parameter(0), metadata={{op_name="params['w']"}}
  %cache_0__0_.1 = {plane} parameter(1), sharding={{replicated}}, metadata={{op_name="cache[0][0]"}}
  %cache_0__1_.1 = {plane} parameter(2), sharding={{replicated}}, metadata={{op_name="cache[0][1]"}}
  %c.0 = s32[]{{:T(128)}} constant(0)
  %dynamic_update_slice.1 = {plane} dynamic-update-slice(%cache_0__0_.1, %new.1, %c.0, %c.0, %pos.1, %c.0), backend_config={{"indices_config":{{"is_index_aligned":[{aligned}]}}}}
  %dynamic_update_slice.2 = {plane} dynamic-update-slice(%cache_0__1_.1, %new.2, %c.0, %c.0, %pos.1, %c.0), backend_config={{"indices_config":{{"is_index_aligned":[{aligned}]}}}}
{body}  ROOT %tuple.1 = ({plane}, {plane}) tuple(%dynamic_update_slice.1, %dynamic_update_slice.2)
}}
"""


def test_lane_major_planes_are_reported_as_faults(tool):
    shape = (32, 25, 1024, 64)
    row = "bf16[1,25,1024,64]"
    body = (f"  %slice.1 = {row}{{2,3,1,0:T(8,128)(2,1)}} fusion(%cache_0__0_.1), kind=kLoop\n"
            f"  %copy.1 = {row}{{3,2,1,0:T(8,128)(2,1)}} copy(%slice.1)\n"
            f"  %copy.2 = {row}{{2,3,1,0:T(8,128)(2,1)}} copy(%copy.1)\n")
    facts = tool.inspect(_hlo(shape, "2,3,1,0", "true,true,false,true", body),
                         {shape})
    assert facts["planes"] == [{"shape": list(shape),
                                "minor_to_major": [2, 3, 1, 0], "count": 2}]
    assert facts["planes_aliased"] == facts["planes_total"] == 2
    assert facts["writes"] == [{"minor_to_major": [2, 3, 1, 0],
                                "unaligned_index_dims": [2],
                                "traced_index_dims": [2],
                                "on_minor_most": True, "count": 2}]
    assert facts["row_relayout_copies"] == 2
    faults = tool._faults("step", facts)
    assert len(faults) == 2 and "lane" in faults[0] and "row" in faults[1]


def test_packed_planes_pass(tool):
    shape = (32, 13, 1024, 128)
    facts = tool.inspect(_hlo(shape, "3,2,1,0", "true,true,false,true"),
                         {shape})
    assert facts["writes"][0]["on_minor_most"] is False
    assert facts["whole_plane_copies"] == 0     # copy.9 is not in ENTRY
    assert tool._faults("step", facts) == []


def test_unaliased_plane_and_whole_plane_copy_are_faults(tool):
    shape = (32, 13, 1024, 128)
    plane = "bf16[32,13,1024,128]{3,2,1,0:T(8,128)(2,1)}"
    text = _hlo(shape, "3,2,1,0", "true,true,false,true",
                f"  %copy.3 = {plane} copy(%dynamic_update_slice.1)\n")
    text = text.replace(", {1}: (2, {}, may-alias)", "")
    facts = tool.inspect(text, {shape})
    assert facts["planes_aliased"] == 1 and facts["whole_plane_copies"] == 1
    assert len(tool._faults("step", facts)) == 2


_PLANE = "bf16[32,13,1024,128]{3,2,1,0:T(8,128)(2,1)}"
_CARRY = f"(s32[]{{:T(128)}}, {_PLANE}, {_PLANE})"


def _loop_hlo(operands, body_extra=""):
    """The packed step with the attention's block loop in it (ISSUE 28):
    a ``while`` whose tuple carries the two planes, as the compiler
    prints it."""
    text = _hlo((32, 13, 1024, 128), "3,2,1,0", "true,true,false,true",
                f"  %copy.7 = {_PLANE} copy(%dynamic_update_slice.2)\n"
                f"  %tuple.9 = {_CARRY} tuple({operands})\n"
                f"  %while.1 = {_CARRY} while(%tuple.9), "
                "condition=%cond.1, body=%wide.body.1.sunk\n")
    body = (f"%wide.body.1.sunk (arg: {_CARRY}) -> {_CARRY} {{\n"
            f"  %arg = {_CARRY} parameter(0)\n"
            f"  %get-tuple-element.5 = {_PLANE} get-tuple-element(%arg), index=1\n"
            f"{body_extra}"
            f"  ROOT %tuple.3 = {_CARRY} tuple(%i.1, %get-tuple-element.5, %get-tuple-element.5)\n"
            "}\n\n")
    return text.replace("ENTRY", body + "ENTRY", 1)


@pytest.mark.parametrize("operands,body_extra,loop_copies", [
    ("%lo.1, %dynamic_update_slice.1, %dynamic_update_slice.2", "", 0),
    ("%lo.1, %dynamic_update_slice.1, %copy.7", "", 1),
    ("%lo.1, %dynamic_update_slice.1, %dynamic_update_slice.2",
     f"  %copy.8 = {_PLANE} copy(%get-tuple-element.5)\n", 1),
], ids=["planes-enter-as-they-are", "plane-enters-as-a-copy",
        "plane-copied-in-the-body"])
def test_block_loop_over_the_planes(tool, operands, body_extra, loop_copies):
    facts = tool.inspect(_loop_hlo(operands, body_extra),
                         {(32, 13, 1024, 128)})
    assert facts["while_loops"] == 1
    assert facts["while_plane_copies"] == loop_copies
    assert facts["planes_aliased"] == facts["planes_total"] == 2
    # %copy.7 stands in ENTRY in every case: a whole-plane copy, whether
    # or not the loop takes it
    assert facts["whole_plane_copies"] == 1
    faults = tool._faults("step", facts)
    assert len(faults) == 1 + bool(loop_copies)
    assert any("while loop" in f for f in faults) == bool(loop_copies)


# -- state planes: a cache without columns (ISSUE 31) ------------------------
_STATE = "bf16[3,1,2,64]{3,2,1,0:T(2,128)(2,1)}"
_KV = "bf16[3,1,64,128]{3,2,1,0:T(8,128)(2,1)}"


def _hybrid_hlo(step=True, extra=""):
    """The tiny hybrid model's programs in outline: one conv layer's state
    plane and one attention layer's K plane, both aliased.  The step hands
    the state back whole; the chunk splices one row into it."""
    write = (f"  %fusion.7 = {_STATE} fusion(%cache_0__0_.1, %u.1), kind=kLoop\n"
             if step else
             f"  %dynamic_update_slice.3 = {_STATE} dynamic-update-slice("
             "%cache_0__0_.1, %row.1, %rowidx.1, %c.0, %c.0, %c.0), "
             'backend_config={"indices_config":{"is_index_aligned":'
             "[false,true,true,true]}}\n")
    out = "%fusion.7" if step else "%dynamic_update_slice.3"
    return f"""HloModule jit_step, is_scheduled=true, input_output_alias={{ {{0}}: (0, {{}}, may-alias), {{1}}: (1, {{}}, may-alias) }}

ENTRY %main.1 (c0: {_STATE}, c1: {_KV}) -> ({_STATE}, {_KV}) {{
  %cache_0__0_.1 = {_STATE} parameter(0), sharding={{replicated}}, metadata={{op_name="cache[0][0]"}}
  %cache_1__0_.1 = {_KV} parameter(1), sharding={{replicated}}, metadata={{op_name="cache[1][0]"}}
  %c.0 = s32[]{{:T(128)}} constant(0)
  %dynamic_update_slice.1 = {_KV} dynamic-update-slice(%cache_1__0_.1, %new.1, %c.0, %c.0, %pos.1, %c.0), backend_config={{"indices_config":{{"is_index_aligned":[true,true,false,true]}}}}
{write}{extra}  ROOT %tuple.1 = ({_STATE}, {_KV}) tuple({out}, %dynamic_update_slice.1)
}}
"""


_SHAPES = {(3, 1, 2, 64), (3, 1, 64, 128)}


@pytest.mark.parametrize("step", [True, False], ids=["step", "chunk"])
def test_state_planes_are_reported_apart(tool, step):
    facts = tool.inspect(_hybrid_hlo(step), _SHAPES, {(3, 1, 2, 64)})
    assert facts["state_planes"] == [{"shape": [3, 1, 2, 64],
                                      "dtype": "bf16",
                                      "minor_to_major": [3, 2, 1, 0],
                                      "count": 1, "bytes": 3 * 2 * 64 * 2}]
    assert facts["planes"] == [{"shape": [3, 1, 64, 128],
                                "minor_to_major": [3, 2, 1, 0], "count": 1}]
    assert facts["state_planes_aliased"] == 1
    assert facts["planes_aliased"] == facts["planes_total"] == 2
    # the K plane's write carries its index on the columns; the chunk's
    # splice of a state row on the rows; the step writes no slice of it
    assert [w["unaligned_index_dims"] for w in facts["writes"]] == [[2]]
    if step:
        assert facts["state_writes"] == "none in ENTRY"
    else:
        assert facts["state_writes"] == [{
            "minor_to_major": [3, 2, 1, 0], "unaligned_index_dims": [0],
            "traced_index_dims": [0], "on_minor_most": False, "count": 1}]
    assert facts["state_plane_copies"] == facts["whole_plane_copies"] == 0
    assert facts["state_row_relayout_copies"] == 0
    assert tool._faults("step", facts) == []


@pytest.mark.parametrize("relaid", [0, 2], ids=["rows-as-they-lie",
                                                "rows-relaid"])
def test_a_float32_summed_state_beside_bfloat16_planes(tool, relaid):
    """A state-space layer keeps a float32 ``[S, heads, head_dim, state]``
    plane beside bfloat16 ones (ISSUE 40): reported with its dtype and its
    bytes, aliased like any plane; a layout-changing copy of ONE row of it
    (the chunk computing on the row it cut out) is counted and a fault, a
    copy that keeps the layout is neither."""
    state = "f32[3,4,16,16]{3,2,1,0:T(8,128)}"
    row = "f32[1,4,16,16]{3,2,1,0:T(8,128)}"
    turned = "f32[1,4,16,16]{2,3,1,0:T(8,128)}"
    extra = (f"  %slice.1 = {row} fusion(%cache_0__0_.1), kind=kLoop\n"
             f"  %copy.1 = {row} copy(%slice.1)\n")
    if relaid:
        extra += (f"  %copy.2 = {turned} copy(%slice.1)\n"
                  f"  %copy.3 = {row} copy(%copy.2)\n")
    text = _hybrid_hlo(step=False, extra=extra).replace(_STATE, state) \
        .replace("bf16[1,1,2,64]", "f32[1,4,16,16]")
    shapes = {(3, 4, 16, 16), (3, 1, 64, 128)}
    facts = tool.inspect(text, shapes, {(3, 4, 16, 16)})
    assert facts["state_planes"] == [{
        "shape": [3, 4, 16, 16], "dtype": "f32",
        "minor_to_major": [3, 2, 1, 0], "count": 1,
        "bytes": 3 * 4 * 16 * 16 * 4}]
    assert facts["state_planes_aliased"] == 1
    assert facts["planes_aliased"] == facts["planes_total"] == 2
    assert facts["state_row_relayout_copies"] == relaid
    assert facts["row_relayout_copies"] == 0         # counted apart
    faults = tool._faults("chunk", facts)
    assert len(faults) == bool(relaid)
    assert all("row of a state plane" in f for f in faults)


def test_a_state_plane_copied_whole_is_a_fault(tool):
    """What a per-row blend that the compiler cannot do in place, or a
    relayout on the way to the step's operands, looks like."""
    other = _STATE.replace("{3,2,1,0:T(2,128)", "{3,0,2,1:T(8,128)")
    text = _hybrid_hlo(extra=f"  %copy.51 = {other} copy(%cache_0__0_.1)\n"
                             f"  %copy.49 = {_STATE} copy(%fusion.9)\n")
    facts = tool.inspect(text, _SHAPES, {(3, 1, 2, 64)})
    assert facts["state_plane_copies"] == 2
    assert facts["whole_plane_copies"] == 0          # counted apart
    faults = tool._faults("step", facts)
    assert len(faults) == 1 and "state plane" in faults[0]
    # a model without such layers reports as before
    assert "state_planes" not in tool.inspect(text, _SHAPES)


def test_a_pooled_key_plane_beside_its_kv_planes(tool):
    """A block-sparse layer keeps a third plane, an entry every 16 columns
    (ISSUE 44): listed with the K/V planes by its own shape, its write's
    traced index on the entries (dimension 2, not the lanes), aliased; the
    chunk's copies of it whole, on its way to another memory space and
    back, are a fault that names its shape and bytes, so that it is told
    from a copy of the K plane sixteen times its size."""
    pooled = "bf16[3,1,4,128]{3,2,1,0:T(8,128)(2,1)}"
    there = pooled.replace("(2,1)}", "(2,1)S(1)}")
    write = (f"  %dynamic_update_slice.5 = {pooled} dynamic-update-slice("
             "%cache_0__0_.1, %new.5, %c.0, %c.0, %entry.1, %c.0), "
             'backend_config={"indices_config":{"is_index_aligned":'
             "[true,true,false,true]}}\n")

    def text(extra=""):
        return _hybrid_hlo(step=True, extra=write + extra) \
            .replace(_STATE, pooled) \
            .replace("tuple(%fusion.7", "tuple(%dynamic_update_slice.5")
    shapes = {(3, 1, 4, 128), (3, 1, 64, 128)}
    facts = tool.inspect(text(), shapes)
    assert sorted(p["shape"] for p in facts["planes"]) \
        == [[3, 1, 4, 128], [3, 1, 64, 128]]
    assert facts["planes_aliased"] == facts["planes_total"] == 2
    assert sum(w["count"] for w in facts["writes"]) == 2
    assert all(w["unaligned_index_dims"] == [2] and not w["on_minor_most"]
               for w in facts["writes"])
    assert facts["whole_plane_copies"] == 0 and tool._faults("step", facts) == []
    facts = tool.inspect(text(
        f"  %copy.61 = {there} copy(%custom-call.5)\n"
        f"  %copy.62 = {there} copy(%copy.61)\n"), shapes)
    assert facts["whole_plane_copied"] == [
        {"shape": [3, 1, 4, 128], "count": 2, "mb": 0.0}]
    faults = tool._faults("chunk", facts)
    assert len(faults) == 1 and "[3, 1, 4, 128]" in faults[0]


# -- weights copied again in every run (ISSUE 30) ----------------------------
_W = "bf16[1600,1600]"
_SLICE = "(bf16[400,1600]{1,0:T(8,128)(2,1)S(1)}, bf16[1600,1600]{1,0:T(8,128)(2,1)}, u32[]{:S(2)})"


def _weights_hlo(q_layout, table_layout, extra=""):
    """The recorded shape of the parent's step program: a projection
    weight prefetched in slices, concatenated and TRANSPOSED, and the
    160 MB table copied on its way to the tied head; ``q_layout`` and
    ``table_layout`` are the layouts the parameters arrive in."""
    q_copy = "" if q_layout == "0,1" else (
        f"  %copy.196 = {_W}{{0,1:T(8,128)(2,1)S(1)}} copy(%custom-call.100), "
        "metadata={op_name=\"params[\\'encoder.layers.0.self_attn.k_proj.weight\\']\"}\n")
    t_copy = "" if table_layout == "1,0" else (
        "  %copy.194 = bf16[50257,1600]{1,0:T(8,128)(2,1)} "
        "copy(%params__wte_weight__.1), sharding={replicated}\n")
    return f"""HloModule jit_step, is_scheduled=true

ENTRY %main.1 (a: {_W}, b: bf16[50257,1600], c: bf16[32,25,1,64]) -> bf16[4] {{
  %params__k__.1 = {_W}{{{q_layout}:T(8,128)(2,1)}} parameter(0), sharding={{replicated}}, metadata={{op_name="params[\\'encoder.layers.0.self_attn.k_proj.weight\\']"}}
  %params__wte_weight__.1 = bf16[50257,1600]{{{table_layout}:T(8,128)(2,1)}} parameter(1), sharding={{replicated}}, metadata={{op_name="params[\\'wte.weight\\']"}}
  %cache.1 = bf16[32,25,1,64]{{3,2,1,0:T(8,128)(2,1)}} parameter(2), metadata={{op_name="cache[0][0]"}}
  %slice-start.384 = {_SLICE} slice-start(%params__k__.1), slice={{[0:400], [0:1600]}}
  %slice-done.384 = bf16[400,1600]{{1,0:T(8,128)(2,1)S(1)}} slice-done(%slice-start.384)
  %custom-call.100 = {_W}{{1,0:T(8,128)(2,1)S(1)}} custom-call(%slice-done.384, %slice-done.384), custom_call_target="ConcatBitcast"
{q_copy}{t_copy}  %copy.9 = bf16[32,25,1,64]{{3,1,0,2:T(8,128)(2,1)S(1)}} copy(%cache.1)
  %copy.10 = {_W}{{0,1:T(8,128)(2,1)}} copy(%fusion.3)
{extra}  ROOT %r = bf16[4]{{0}} fusion(%copy.9), kind=kLoop
}}
"""


@pytest.mark.parametrize("q_layout,table_layout,names,mb", [
    ("1,0", "0,1", ["encoder.layers.0.self_attn.k_proj.weight",
                    "wte.weight"], 5.12 + 160.8224),
    ("0,1", "0,1", ["wte.weight"], 160.8224),
    ("0,1", "1,0", [], 0.0),
], ids=["default-layouts", "projection-relaid", "both-relaid"])
def test_weight_copies_follow_the_prefetch_to_the_parameter(
        tool, q_layout, table_layout, names, mb):
    got = tool.weight_copies(_weights_hlo(q_layout, table_layout), n_state=2)
    # the cache's copy is an activation's (parameter 2 is no state), the
    # fusion's copy does not start at a parameter
    assert sorted(n.split("'")[1] for n, _ in got) == sorted(names)
    assert sum(m for _, m in got) == pytest.approx(mb)


def test_small_weight_copies_are_not_counted(tool):
    text = _weights_hlo("0,1", "1,0",
                        "  %copy.11 = bf16[1,1600]{1,0:T(2,128)(2,1)} "
                        "copy(%params__k__.1)\n")
    assert tool.weight_copies(text, n_state=2) == []
    assert len(tool.weight_copies(text, n_state=2, min_mb=0.001)) == 1


# -- the row write that activates a row (ISSUE 32) ----------------------------
_LOGITS = "f32[32,50257]{1,0:T(8,128)}"


def _put_row_hlo(alias="{}: (0, {}, may-alias)", extra="",
                 operand="%logits.1"):
    """The recorded shape of ``put_logits_row`` for a v5e: the one output
    is no tuple, so its alias reads ``{}: (0, ...``."""
    return f"""HloModule jit_put, is_scheduled=true, input_output_alias={{ {alias} }}, entry_computation_layout={{({_LOGITS}, f32[50257]{{0:T(1024)}}, s32[]{{:T(128)}})->{_LOGITS}}}

ENTRY %main.1 (logits.1: f32[32,50257], row.1: f32[50257], rowidx.1: s32[]) -> f32[32,50257] {{
  %rowidx.1 = s32[]{{:T(128)}} parameter(2), sharding={{replicated}}, metadata={{op_name="rowidx"}}
  %row.1 = f32[50257]{{0:T(1024)}} parameter(1), sharding={{replicated}}, metadata={{op_name="row"}}
  %logits.1 = {_LOGITS} parameter(0), sharding={{replicated}}, metadata={{op_name="logits"}}
  %broadcast_in_dim.1 = f32[1,50257]{{1,0:T(1,128)S(1)}} reshape(%row.1)
{extra}  ROOT %dynamic_update_slice.1 = {_LOGITS} dynamic-update-slice({operand}, %broadcast_in_dim.1, %rowidx.1, %constant.3), backend_config={{"indices_config":{{"is_index_aligned":[false,true]}}}}
}}
"""


@pytest.mark.parametrize("kw,aliased,copies", [
    ({}, True, 0),
    ({"alias": ""}, False, 0),
    ({"extra": f"  %copy.1 = {_LOGITS} copy(%logits.1)\n",
      "operand": "%copy.1"}, False, 1),
], ids=["in-place", "not-donated", "plane-copied-first"])
def test_the_row_write_is_in_place_or_a_fault(tool, kw, aliased, copies):
    facts = tool.logits_put(_put_row_hlo(**kw), (32, 50257))
    assert facts == {"logits_aliased": aliased, "logits_plane_copies": copies}
    # a tuple's aliases still read as they did
    assert tool._aliased_params(_hlo((2, 2, 8, 128), "3,2,1,0",
                                     "true,true,false,true")) == {1, 2}


# -- a chunk's row: cut out and spliced back, or addressed in place (ISSUE 46)
_ROW = "bf16[1,13,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}"
_BLOCK = "bf16[1,13,16,128]{3,2,1,0:T(8,128)(2,1)S(1)}"
_ALIGNED = ('backend_config={"indices_config":{"is_index_aligned":'
            '[true,true,false,true]}}')


def _chunk_hlo(entry, computations=""):
    """A chunk over ONE K plane in outline, as the compiler prints it for
    ``gpt2-xl-serve``: ``entry`` are ENTRY's instructions between the
    parameters and the ROOT, which hands back ``%out``."""
    return f"""HloModule jit_chunk, is_scheduled=true, input_output_alias={{ {{0}}: (0, {{}}, may-alias) }}

{computations}ENTRY %main.1 (c0: {_PLANE}) -> ({_PLANE}) {{
  %cache_0__0_.1 = {_PLANE} parameter(0), sharding={{replicated}}, metadata={{op_name="cache[0][0]"}}
  %row.1 = s32[]{{:T(128)S(6)}} parameter(1)
  %pos.1 = s32[]{{:T(128)S(6)}} parameter(2)
  %c.0 = s32[]{{:T(128)}} constant(0)
{entry}  ROOT %tuple.1 = ({_PLANE}) tuple(%out)
}}
"""


# the sliced form: a fusion cuts the row, the block is written into the
# ROW, a fusion splices the row back into the plane
_SLICED = _chunk_hlo(
    f"  %cut.1 = {_ROW} fusion(%cache_0__0_.1, %row.1), kind=kLoop, calls=%fused_cut\n"
    f"  %dynamic_update_slice.1 = {_ROW} dynamic-update-slice(%cut.1, %new.1, %c.0, %c.0, %pos.1, %c.0), {_ALIGNED}\n"
    f"  %out = {_PLANE} fusion(%cache_0__0_.1, %dynamic_update_slice.1, %row.1), kind=kLoop, calls=%fused_splice\n",
    f"""%fused_cut (p0: {_PLANE}, p1: s32[]) -> {_ROW} {{
  %p0 = {_PLANE} parameter(0)
  %p1 = s32[]{{:T(128)S(6)}} parameter(1)
  %z = s32[]{{:T(128)}} constant(0)
  ROOT %dynamic_slice.1 = {_ROW} dynamic-slice(%p0, %p1, %z, %z, %z), dynamic_slice_sizes={{1,13,1024,128}}
}}

%fused_splice (q0: {_PLANE}, q1: {_ROW}, q2: s32[]) -> {_PLANE} {{
  %q0 = {_PLANE} parameter(0)
  %q1 = {_ROW} parameter(1)
  %q2 = s32[]{{:T(128)S(6)}} parameter(2)
  %z.1 = s32[]{{:T(128)}} constant(0)
  ROOT %dynamic_update_slice.9 = {_PLANE} dynamic-update-slice(%q0, %q1, %q2, %z.1, %z.1, %z.1)
}}

""")

# in place: the block goes into the plane at (row, 0, pos, 0); the row's
# dynamic-slice is an operand of the product, nested inside its fusion
_IN_PLACE = _chunk_hlo(
    f"  %out = {_PLANE} dynamic-update-slice(%cache_0__0_.1, %new.1, %row.1, %c.0, %pos.1, %c.0), {_ALIGNED}\n"
    "  %attend.1 = bf16[13,16,128]{2,1,0:T(8,128)(2,1)S(1)} fusion(%probs.1, %out, %row.1), kind=kOutput, calls=%fused_product\n",
    f"""%fused_read (r0: {_PLANE}, r1: s32[]) -> bf16[13,1024,128,1] {{
  %r0 = {_PLANE} parameter(0)
  %r1 = s32[]{{:T(128)S(6)}} parameter(1)
  %z.2 = s32[]{{:T(128)}} constant(0)
  %dynamic_slice.2 = bf16[1,13,1024,128]{{3,2,1,0:T(8,128)(2,1)}} dynamic-slice(%r0, %r1, %z.2, %z.2, %z.2), dynamic_slice_sizes={{1,13,1024,128}}
  ROOT %bitcast.1 = bf16[13,1024,128,1]{{2,1,3,0:T(8,128)(2,1)}} bitcast(%dynamic_slice.2)
}}

%fused_product (s0: bf16[13,2,16,1024], s1: {_PLANE}, s2: s32[]) -> bf16[13,16,128] {{
  %s0 = bf16[13,2,16,1024]{{3,2,1,0:T(8,128)(2,1)}} parameter(0)
  %s1 = {_PLANE} parameter(1)
  %s2 = s32[]{{:T(128)S(6)}} parameter(2)
  %fusion.5 = bf16[13,1024,128,1]{{2,1,3,0:T(8,128)(2,1)}} fusion(%s1, %s2), kind=kLoop, calls=%fused_read
  ROOT %convolution.1 = bf16[13,16,128]{{2,1,0:T(8,128)(2,1)}} convolution(%s0, %fusion.5), dim_labels=01bf_0io1->01bf
}}

""")

# a row cut out by an instruction of ENTRY's own, nothing fused
_BARE = _chunk_hlo(
    f"  %dynamic_slice.3 = {_ROW} dynamic-slice(%cache_0__0_.1, %row.1, %c.0, %c.0, %c.0), dynamic_slice_sizes={{1,13,1024,128}}\n"
    f"  %dynamic_update_slice.1 = {_ROW} dynamic-update-slice(%dynamic_slice.3, %new.1, %c.0, %c.0, %pos.1, %c.0), {_ALIGNED}\n"
    f"  %out = {_PLANE} dynamic-update-slice(%cache_0__0_.1, %dynamic_update_slice.1, %row.1, %c.0, %c.0, %c.0), {_ALIGNED}\n")


@pytest.mark.parametrize("text,slices,traced", [
    (_SLICED, 2, [[2]]),
    (_IN_PLACE, 0, [[0, 2]]),
    (_BARE, 2, [[2], [0]]),
], ids=["row-cut-and-spliced-by-fusions", "block-written-in-place",
        "row-cut-and-spliced-in-entry"])
def test_row_sized_slices_count_a_cut_row_and_not_an_in_place_block(
        tool, text, slices, traced):
    facts = tool.inspect(text, {(32, 13, 1024, 128)})
    assert facts["row_sized_slices"] == slices
    assert [w["traced_index_dims"] for w in facts["writes"]] == traced
    assert not any(w["on_minor_most"] for w in facts["writes"])
    assert facts["planes_aliased"] == facts["planes_total"] == 1
    assert facts["whole_plane_copies"] == 0
    # reported, never a fault: six configurations still cut their row
    assert tool._faults("chunk", facts) == []


def test_a_block_sized_slice_of_a_row_is_no_row_sized_slice(tool):
    """The wrap-aware write reads the ``T`` current columns of its row
    (``ring_block_write``'s blend): a block, not a row."""
    text = _chunk_hlo(
        f"  %cur.1 = {_BLOCK} dynamic-slice(%cache_0__0_.1, %row.1, %c.0, %pos.1, %c.0), dynamic_slice_sizes={{1,13,16,128}}\n"
        f"  %out = {_PLANE} dynamic-update-slice(%cache_0__0_.1, %cur.1, %row.1, %c.0, %pos.1, %c.0), {_ALIGNED}\n")
    assert tool.row_sized_slices(text, {(32, 13, 1024, 128)}) == 0


def _serving_configs():
    import glob
    import json
    root = os.path.dirname(os.path.dirname(_TOOL))
    out = []
    for path in sorted(glob.glob(os.path.join(root, "benchmark", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if "serve" in cfg:
            out.append(cfg)
    return out


@pytest.mark.parametrize("cfg", _serving_configs(),
                         ids=[c["name"] for c in _serving_configs()])
def test_every_serving_configuration_is_taken_by_name(cfg):
    """The tool (and ``tools/program_text_hash.py``) finds a configuration's
    model by the ``family`` the file names, as the runners do: the module
    has what ``main`` asks of it, and ``serve`` the three sizes it reads.
    No table, no option a family."""
    import importlib
    family = importlib.import_module("benchmark.models." + cfg["family"])
    assert callable(family.build_unweighted) and callable(family.leaf_ids)
    sv = cfg["serve"]
    assert all(int(sv[k]) > 0 for k in ("slots", "max_len", "prefill_chunk"))
    assert sv["max_len"] % sv["prefill_chunk"] == 0
    pc = family.program_config(cfg)
    assert pc.vocab_size == cfg["vocab_size"]

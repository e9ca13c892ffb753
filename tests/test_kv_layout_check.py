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

#!/usr/bin/env python3
"""Read the ring cache's DEVICE layout back from the compiled programs.

The logical shape of a ring plane says nothing about where its columns
lie on the chip: the TPU compiler picks the device layout from the shape
(nn/layer/transformer.py ``ring_block_write``).  This tool lowers the slot
loop's step and chunk programs of a serving config for a DESCRIBED
``v5e:2x2`` with the TPU compiler installed here (no chip, ~15 s a
program) and prints, from ``compiled.as_text()``:

  * per distinct cache plane: logical shape, device layout
    (minor-to-major), and how many planes have it;
  * for each write (``dynamic-update-slice`` into a plane or into a
    ``[1, ., C, .]`` row of one): which dimension carries the traced,
    unaligned index and whether that is the layout's minor-most (lane)
    dimension;
  * whether every plane is aliased input to output (donation kept), and
    the program's ``memory_analysis()`` (arguments, temporaries), which is
    how a configuration's ``slots`` is sized before any chip time;
  * in each program, the ``copy``/``transpose`` instructions of a whole
    plane, and the copies of a cache row ``[1, ., C, .]`` whose operand
    has another layout (the relayout a lane-major plane forces on the
    chunk's block write);
  * the ``while`` loops of each program (the decode attention reads the
    planes in column blocks under one, ``cached_attention``; the step's
    line says how wide a block is): whether a plane enters one as a
    copy, or is copied inside its body.

    JAX_PLATFORMS=cpu python3 tools/kv_layout_check.py gpt2-xl-serve [slots]

Exit code 1 when a write's traced index lies on the minor-most dimension,
a plane is not aliased, a whole plane is copied (on its way into a loop
and inside one too), or a cache row changes layout.  Run by hand, one
process at a time: only one process may load libtpu, so this is not a
pytest file.
"""
from __future__ import annotations

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# "%name = bf16[32,13,1024,128]{3,2,1,0:T(8,128)(2,1)} opcode(operands...)"
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"\{(?P<layout>[\d,]*)[^}]*\} (?P<op>[\w\-]+)\((?P<args>[^)]*)\)")


# "%while.48 = (s32[], ..) while(%tuple.1252), condition=%c, body=%b" and
# the "%tuple.1252 = (..) tuple(%a, %b, ..)" it takes
_WHILE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \(.*\) while\(%(?P<arg>[\w.\-]+)"
                    r"\), condition=%[\w.\-]+, body=%(?P<body>[\w.\-]+)")
_TUPLE = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = \(.*\) "
                    r"tuple\((?P<args>[^)]*)\)")


def _body(hlo_text, name=None):
    """The lines of one computation: ``name``'s, or ENTRY's."""
    lines = hlo_text.splitlines()
    head = "ENTRY" if name is None else f"%{name} ("
    start = next(i for i, l in enumerate(lines) if l.startswith(head))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return lines[start + 1:end]


def _entry(hlo_text, name=None):
    """{name: (dims, minor_to_major, opcode, operand names, line)} of the
    ENTRY computation's array-valued instructions (or of ``name``'s)."""
    out = collections.OrderedDict()
    for line in _body(hlo_text, name):
        m = _INSTR.match(line)
        if m is None:
            continue
        dims = tuple(int(d) for d in m["dims"].split(",") if d)
        layout = tuple(int(d) for d in m["layout"].split(",") if d)
        args = re.findall(r"%([\w.\-]+)", m["args"])
        out[m["name"]] = (dims, layout, m["op"], args, line)
    return out


def _while_plane_copies(hlo_text, instrs, plane_shapes):
    """(loops of ENTRY, [copies]): the operands of a loop's tuple that are
    a copy or transpose of a whole plane, and such instructions inside the
    loop's body."""
    lines = _body(hlo_text)
    tuples = {m["name"]: re.findall(r"%([\w.\-]+)", m["args"])
              for m in map(_TUPLE.match, lines) if m}
    loops, copies = 0, []
    for m in filter(None, map(_WHILE.match, lines)):
        loops += 1
        inside = _entry(hlo_text, m["body"])
        for where, names in ((instrs, tuples.get(m["arg"], ())),
                             (inside, inside)):
            copies += [n for n in names if n in where
                       and where[n][0] in plane_shapes
                       and where[n][2] in ("copy", "transpose")]
    return loops, copies


def _aliased_params(hlo_text):
    head = hlo_text.split("\n", 1)[0]
    return {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}", head)}


def inspect(hlo_text, plane_shapes):
    """The facts above for one compiled program, as a dict."""
    instrs = _entry(hlo_text)
    planes = {}                      # instruction name -> parameter number
    for name, (dims, layout, op, _args, line) in instrs.items():
        if op == "parameter" and 'op_name="cache[' in line \
                and dims in plane_shapes:
            planes[name] = int(re.search(r"parameter\((\d+)\)", line)[1])
    layouts = collections.Counter(
        (instrs[n][0], instrs[n][1]) for n in planes)
    aliased = _aliased_params(hlo_text)
    rows = {(1,) + s[1:] for s in plane_shapes}
    writes = collections.Counter()
    for name, (dims, layout, op, args, line) in instrs.items():
        if op != "dynamic-update-slice" or \
                not (dims in plane_shapes or dims in rows):
            continue
        m = re.search(r'"is_index_aligned":\[([\w,]*)\]', line)
        unaligned = tuple(i for i, a in enumerate(m[1].split(","))
                          if a != "true") if m else ()
        writes[(layout, unaligned)] += 1
    plane_copies, row_relayouts = [], []
    for name, (dims, layout, op, args, _line) in instrs.items():
        if op not in ("copy", "transpose") or not args:
            continue
        src = instrs.get(args[0])
        if dims in plane_shapes:
            plane_copies.append(name)
        elif dims in rows and src is not None and src[1] != layout:
            row_relayouts.append(name)
    loops, loop_copies = _while_plane_copies(hlo_text, instrs, plane_shapes)
    return {
        "while_loops": loops,
        "while_plane_copies": len(loop_copies),
        "planes": [{"shape": list(s), "minor_to_major": list(l), "count": c}
                   for (s, l), c in layouts.items()],
        "planes_aliased": sum(1 for p in planes.values() if p in aliased),
        "planes_total": len(planes),
        "writes": [{"minor_to_major": list(l),
                    "unaligned_index_dims": list(u),
                    "on_minor_most": bool(l) and l[0] in u, "count": c}
                   for (l, u), c in writes.items()],
        "whole_plane_copies": len(plane_copies),
        "row_relayout_copies": len(row_relayouts),
    }


def _faults(what, facts):
    out = []
    if facts["planes_aliased"] != facts["planes_total"]:
        out.append(f"{what}: {facts['planes_total'] - facts['planes_aliased']}"
                   " cache planes are not aliased input to output")
    for w in facts["writes"]:
        if w["on_minor_most"]:
            out.append(f"{what}: {w['count']} writes carry their traced "
                       f"index on the minor-most (lane) dimension, layout "
                       f"{w['minor_to_major']}")
    if facts["whole_plane_copies"]:
        out.append(f"{what}: {facts['whole_plane_copies']} copies or "
                   "transposes of a whole plane")
    if facts["while_plane_copies"]:
        out.append(f"{what}: {facts['while_plane_copies']} whole planes "
                   "copied into a while loop or inside one")
    if facts["row_relayout_copies"]:
        out.append(f"{what}: {facts['row_relayout_copies']} layout-changing "
                   "copies of a cache row")
    return out


def main(argv):
    if len(argv) not in (1, 2):
        print(__doc__, file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import importlib
    from paddle_tpu.nn.functional.attention import decode_block
    from paddle_tpu.text.generation import Generator
    with open(os.path.join(ROOT, "benchmark", "configs",
                           argv[0] + ".json")) as f:
        cfg = json.load(f)
    # the configuration names its model family, as for the runners
    family = importlib.import_module("benchmark.models." + cfg["family"])
    sv = cfg["serve"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    gen = Generator(family.build_unweighted(cfg),
                    seq_buckets=sv["seq_buckets"], max_len=sv["max_len"])
    state = place(gen._state_avals())
    S, C, T = sv["slots"], sv["max_len"], sv["prefill_chunk"]
    if len(argv) > 1:
        S = int(argv[1])            # try another slot count
    plane_shapes = {tuple(p.shape) for c in gen.slot_cache_avals_all(S, C)
                    for p in c}
    faults = []
    for what, fn, avals in (
            ("step", gen._build_step(S, C, -1), gen.step_avals(S, C)),
            ("chunk", gen._build_chunk(S, T, C), gen.chunk_avals(S, T, C))):
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(
            *state, *place(avals)).compile()
        facts = inspect(compiled.as_text(), plane_shapes)
        if what == "step" and "kv" in gen.plane_kinds():
            # the column blocks of the step's attention (cached_attention)
            facts = {"attn_block": decode_block(C), **facts}
        print(json.dumps({"config": cfg["name"], "program": what,
                          "slots": S, "cache": C, **facts}), flush=True)
        mem = compiled.memory_analysis()
        print(json.dumps({"program": what, "memory_gib": {
            k: round(getattr(mem, k + "_size_in_bytes") / 2 ** 30, 3)
            for k in ("argument", "output", "alias", "temp",
                      "generated_code")}}), flush=True)
        faults += _faults(what, facts)
    for f in faults:
        print("FAULT " + f, flush=True)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

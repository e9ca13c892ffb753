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
    dimension, and ``traced_index_dims``, every dimension whose index is
    no constant (a step's column write: ``[2]``; a chunk that writes its
    block into the full plane in place: ``[0, 2]``, row and column);
  * ``row_sized_slices``: how many whole cache rows ``[1, ., C, .]`` a
    program cuts out of a plane or writes back into one, as instructions
    of their own or inside a fusion (``dynamic-slice`` whose row is the
    instruction's result, ``dynamic-update-slice`` whose update is a
    row): what a chunk pays that runs on a cut-out row
    (``Generator.chunk_row() == "sliced"``), 2 a plane; 0 for one that
    addresses the row inside the plane (``"in_place"``), where the row's
    ``dynamic-slice`` is an operand of the attention's product, fused
    into it, and never a 3.4 MB array of its own;
  * whether every plane is aliased input to output (donation kept), and
    the program's ``memory_analysis()`` (arguments, temporaries), which is
    how a configuration's ``slots`` is sized before any chip time;
  * in each program, the ``copy``/``transpose`` instructions of a whole
    plane, and the copies of a cache row ``[1, ., C, .]`` whose operand
    has another layout (the relayout a lane-major plane forces on the
    chunk's block write);
  * the STATE planes apart (a layer whose ``cache_spec`` has no columns: a
    short convolution's last inputs, ``[S, 1, L-1, hidden]``, and a
    state-space layer's summed state or a linear-attention layer's matrix
    state, float32 ``[S, heads, head_dim, state]`` beside planes of another
    dtype): their dtype, bytes and
    layout, whether each is aliased in place, which dimension carries the
    index of a write into one (the chunk splices ONE row, index on the
    row dimension, where the compiler leaves that splice an instruction
    of its own; the step writes no slice: it hands back the whole plane
    with the rows it did not feed as they were), the copies of a whole
    state plane in either program, and the layout-changing copies of ONE
    row of a state plane (``state_row_relayout_copies``: what the chunk
    pays to compute on the row it cut out);
  * the ``while`` loops of each program (the decode attention reads the
    planes in column blocks, ``cached_attention``; the step's line says
    how wide a block is and, ``step_read``, in which form: ``"span"``,
    every row the union span under two loops, or ``"per_row"``, each
    generating row its own blocks in one kernel, the planes handed to the
    custom call as they lie and no loop in the text): whether a plane
    enters a loop as a copy, or is copied inside its body;
  * for a latent-attention model, ``latent_form`` beside each program: the
    form its cached attention takes at that program's block width
    (``absorbed`` for the step's one query a row; for a wide chunk
    ``per_head_fused``, the per-head form as ONE Pallas kernel, where the
    shapes take it, else ``per_head``, the XLA loop:
    ``LatentAttention.cached_form``);
  * ``weight_copies``: the ``copy``/``transpose`` instructions of at least
    1 MB whose operand chain starts at a parameter of the model (a weight
    transposed again in every run), with their MB; ``weight_copies_default``
    is the same count for the weights in their default layouts (the
    programs ``Generator.step_exec`` / ``chunk_exec`` compile alone);
  * for a configuration served with the prefix cache on (``serve.
    prefix_cache``): that the cache admits its planes (decided from
    ``cache_spec``; the first line prints ``plane_kinds``, a ``latent``
    plane on its own or with its selector-key plane among them), the
    line ``prefix_cache`` (the planes and bytes of ONE block, and how many
    blocks ``serve.prefix_cache_hbm_mb`` holds: what lies on the device
    beside the programs' arguments when ``slots`` is sized), and the same
    facts for its two data movers, ``kv_push_block`` (a cached block
    written into a row: planes aliased, index dimensions, copies) and
    ``kv_pull_block``;
  * the program that activates a row (``Generator.put_logits_row_exec``:
    one row written into the step's ``[S, V]`` logits): ``logits_aliased``,
    whether its output is its donated input, written in place, with no
    copy of the plane beside it;
  * ``scope_instructions``: the instructions a capture would show as ops
    (those of no fused computation and of no reducer), counted by the
    bucket of their ``jax.named_scope`` path (``profiler.ledger.
    parse_scopes``, the parser behind ``program_scopes()``;
    ``benchmark/scope_buckets.json``), and ``unscoped_pct``, the share
    with no bucket: how far the per-scope device times of a traced run
    (PERF.md section 3) will reach, read before chip time is spent.

The two programs are compiled through ``Generator.slot_execs``, the slot
loop's own way to them, so what is checked is what is served: the line
``weights`` says how many weights the pair agreed to have relaid and on
how many the two disagreed.

    JAX_PLATFORMS=cpu python3 tools/kv_layout_check.py gpt2-xl-serve [slots]

Exit code 1 when a write's traced index lies on the minor-most dimension,
a plane is not aliased, a whole plane is copied (on its way into a loop
and inside one too; a state plane too), a cache row changes layout, a
weight on which the two programs did not disagree is still copied, or the
row write copies the logits.  Run by hand, one process at
a time: only one process may load libtpu, so this is not a pytest file.
"""
from __future__ import annotations

import collections
import json
import math
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


# any ENTRY instruction with operands: "%name = <type> opcode(%a, %b, ..)";
# a type holds "T(8,128)" and "S(1)" but never "(%"
_ANY = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = .*? "
                  r"(?P<op>[\w\-]+)\((?P<args>%[^)]*)\)")
_PARAM = re.compile(r"^\s*%(?P<name>[\w.\-]+) = .* parameter\((?P<n>\d+)\)"
                    r".*?op_name=\"(?P<arg>[^\"]*)\"")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
# what a weight passes through on its way from the parameter to the copy:
# the prefetch in slices and their concatenation, views, tuples
_PASS = {"bitcast", "custom-call", "slice-start", "slice-done", "slice",
         "copy-start", "copy-done", "get-tuple-element", "reshape"}


def weight_copies(hlo_text, n_state, min_mb=1.0):
    """[(argument name, MB)] of the ENTRY copies/transposes of at least
    ``min_mb`` MB whose first operand leads, through prefetches and views
    only, back to one of the first ``n_state`` parameters (the model's
    state): a weight that the program lays out again in every run."""
    lines = _body(hlo_text)
    params = {m["name"]: m["arg"].replace("\\'", "'")
              for m in map(_PARAM.match, lines)
              if m and int(m["n"]) < n_state}
    ops = {m["name"]: (m["op"], re.findall(r"%([\w.\-]+)", m["args"]))
           for m in map(_ANY.match, lines) if m}
    out = []
    for name, (dims, _layout, op, args, line) in _entry(hlo_text).items():
        if op not in ("copy", "transpose") or not args:
            continue
        dtype = _INSTR.match(line)["dtype"]
        mb = _BYTES.get(dtype, 4) * math.prod(dims) / 1e6
        src = args[0]
        while src in ops and ops[src][0] in _PASS:
            src = ops[src][1][0]
        if mb >= min_mb and src in params:
            out.append((params[src], mb))
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(", re.M)
_CALLED = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_NO_OP_EVENT = ("parameter", "constant", "get-tuple-element", "tuple",
                "bitcast")


def scope_coverage(hlo_text):
    """({bucket: instructions}, unscoped %) over the instructions of the
    computations that run as ops of their own: not a fusion's nor a
    reducer's (``calls=``, ``to_apply=``), and no parameter, constant,
    tuple or bitcast."""
    from paddle_tpu.profiler import ledger
    from benchmark.layer_metrics import _program_scopes
    _module, table = ledger.parse_scopes(hlo_text)
    inner = set(_CALLED.findall(hlo_text))
    kept, heads = {}, list(_COMPUTATION.finditer(hlo_text))
    for head, nxt in zip(heads, heads[1:] + [None]):
        if head.group(1) in inner:
            continue
        body = hlo_text[head.end():nxt.start() if nxt else len(hlo_text)]
        for line in body.splitlines():
            m = _ANY.match(line) or _INSTR.match(line)
            if m and m["op"] not in _NO_OP_EVENT and m["name"] in table:
                kept[m["name"]] = table[m["name"]]
    counts = _program_scopes.count_buckets(
        kept, _program_scopes.load_buckets())
    none = counts.get(_program_scopes.UNSCOPED, 0)
    return dict(sorted(counts.items())), \
        round(100.0 * none / max(1, sum(counts.values())), 1)


def _computations(hlo_text):
    """{name: its lines} of every computation of the module, and ENTRY's
    name."""
    out, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = out[m.group(1)] = []
            if line.startswith("ENTRY"):
                entry = m.group(1)
        elif line == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return out, entry


# "%name = <type> opcode(operands...)", the type an array's or a tuple's
_TYPED = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>.*?) "
                    r"(?P<op>[\w\-]+)\((?P<args>[^)]*)\)")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _dims(type_text):
    """The dims of every array in a type (one, or a tuple's several)."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _SHAPE.finditer(type_text)]


def row_sized_slices(hlo_text, plane_shapes):
    """How many whole rows ``[1, ., C, .]`` of a cache plane ENTRY cuts out
    or writes back: its ``dynamic-slice`` instructions whose result is a
    row, its ``dynamic-update-slice`` instructions whose update is one,
    and its fusions that do either inside (a cut counts where a row is
    among the fusion's results: a row that only feeds a product of the
    same fusion is read where it lies and is no array of its own)."""
    rows = {(1,) + s[1:] for s in plane_shapes if s[0] != 1}
    comps, entry = _computations(hlo_text)
    parsed = {name: [m for m in map(_TYPED.match, lines) if m]
              for name, lines in comps.items()}

    def n_rows(type_text):
        return sum(d in rows for d in _dims(type_text))

    def counts(name, seen=()):
        """Per instruction of ``name``: (it, rows cut, rows written), what
        it calls counted in."""
        types = {m["name"]: m["type"] for m in parsed[name]}
        for m in parsed[name]:
            args = re.findall(r"%([\w.\-]+)", m["args"])
            cuts = puts = 0
            if m["op"] == "dynamic-slice":
                cuts = min(1, n_rows(m["type"]))
            elif m["op"] == "dynamic-update-slice" and len(args) > 1:
                puts = min(1, n_rows(types.get(args[1], "")))
            for callee in _CALLED.findall(m.string):
                if callee in parsed and callee not in seen:
                    for _m, c, p in counts(callee, seen + (name,)):
                        cuts, puts = cuts + c, puts + p
            yield m, cuts, puts

    # a fusion's cut rows count as far as rows are among its results
    return sum(puts + (min(cuts, n_rows(m["type"])) if m["op"] == "fusion"
                       else cuts)
               for m, cuts, puts in counts(entry))


def _aliased_params(hlo_text):
    # "{1}: (2, {}, may-alias)" of a tuple's element; "{}: (0, ..." where
    # the program's one output is no tuple
    head = hlo_text.split("\n", 1)[0]
    return {int(p) for p in re.findall(r"\{\d*\}: \((\d+), \{\}", head)}


def logits_put(hlo_text, shape):
    """The row write's facts: the ENTRY copies/transposes of a whole
    ``shape`` (the ``[S, V]`` logits), and whether the output is the
    donated first argument with none of them beside it."""
    copies = [n for n, (dims, _l, op, _a, _line) in _entry(hlo_text).items()
              if dims == tuple(shape) and op in ("copy", "transpose")]
    return {"logits_aliased": 0 in _aliased_params(hlo_text) and not copies,
            "logits_plane_copies": len(copies)}


def inspect(hlo_text, plane_shapes, state_shapes=frozenset()):
    """The facts above for one compiled program, as a dict.
    ``state_shapes`` are those of ``plane_shapes`` that have no columns."""
    instrs = _entry(hlo_text)
    state_shapes = set(state_shapes)
    state_rows = {(1,) + s[1:] for s in state_shapes}
    planes = {}                      # instruction name -> parameter number
    for name, (dims, layout, op, _args, line) in instrs.items():
        if op == "parameter" and 'op_name="cache[' in line \
                and dims in plane_shapes:
            planes[name] = int(re.search(r"parameter\((\d+)\)", line)[1])
    layouts = collections.Counter(
        (instrs[n][0], instrs[n][1]) for n in planes
        if instrs[n][0] not in state_shapes)
    state_layouts = collections.Counter(
        (instrs[n][0], instrs[n][1], _INSTR.match(instrs[n][4])["dtype"])
        for n in planes if instrs[n][0] in state_shapes)
    aliased = _aliased_params(hlo_text)
    rows = {(1,) + s[1:] for s in plane_shapes}
    writes, state_writes = collections.Counter(), collections.Counter()
    for name, (dims, layout, op, args, line) in instrs.items():
        if op != "dynamic-update-slice" or \
                not (dims in plane_shapes or dims in rows):
            continue
        m = re.search(r'"is_index_aligned":\[([\w,]*)\]', line)
        unaligned = tuple(i for i, a in enumerate(m[1].split(","))
                          if a != "true") if m else ()
        # the dimensions whose index is no constant: the column alone for
        # a step's write, row and column for a chunk's block written into
        # the full plane (a major dimension's index is always "aligned")
        traced = tuple(i for i, a in enumerate(args[2:])
                       if a not in instrs or instrs[a][2] != "constant")
        (state_writes if dims in state_shapes or dims in state_rows
         else writes)[(layout, unaligned, traced)] += 1
    plane_copies, state_copies, row_relayouts = [], [], []
    state_row_relayouts = []
    for name, (dims, layout, op, args, _line) in instrs.items():
        if op not in ("copy", "transpose") or not args:
            continue
        src = instrs.get(args[0])
        if dims in state_shapes:
            state_copies.append(name)
        elif dims in plane_shapes:
            plane_copies.append(name)
        elif dims in rows and src is not None and src[1] != layout:
            (state_row_relayouts if dims in state_rows
             else row_relayouts).append(name)
    loops, loop_copies = _while_plane_copies(hlo_text, instrs, plane_shapes)
    def as_writes(counter):
        return [{"minor_to_major": list(l), "unaligned_index_dims": list(u),
                 "traced_index_dims": list(t),
                 "on_minor_most": bool(l) and l[0] in u, "count": c}
                for (l, u, t), c in counter.items()]

    state = {} if not state_shapes else {
        "state_planes": [{"shape": list(s), "dtype": d,
                          "minor_to_major": list(l), "count": c,
                          "bytes": c * _BYTES.get(d, 4) * math.prod(s)}
                         for (s, l, d), c in state_layouts.items()],
        "state_planes_aliased": sum(
            1 for n, p in planes.items()
            if instrs[n][0] in state_shapes and p in aliased),
        # no write of a slice among ENTRY's instructions: the program hands
        # the plane back whole, or its splice of a row is fused
        "state_writes": as_writes(state_writes) or "none in ENTRY",
        "state_plane_copies": len(state_copies),
        "state_row_relayout_copies": len(state_row_relayouts)}
    return {
        **state,
        "while_loops": loops,
        "while_plane_copies": len(loop_copies),
        "planes": [{"shape": list(s), "minor_to_major": list(l), "count": c}
                   for (s, l), c in layouts.items()],
        "planes_aliased": sum(1 for p in planes.values() if p in aliased),
        "planes_total": len(planes),
        "writes": as_writes(writes),
        "whole_plane_copies": len(plane_copies),
        # which planes: a pooled-key plane (an entry every 16 columns) is
        # a sixteenth of the K plane beside it
        "whole_plane_copied": [
            {"shape": list(s), "count": c, "mb": round(
                c * _BYTES.get(d, 4) * math.prod(s) / 1e6, 1)}
            for (s, d), c in collections.Counter(
                (instrs[n][0], _INSTR.match(instrs[n][4])["dtype"])
                for n in plane_copies).items()],
        "row_relayout_copies": len(row_relayouts),
        "row_sized_slices": row_sized_slices(hlo_text, plane_shapes),
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
                   "transposes of a whole plane"
                   + "".join(f", {c['count']} of {c['shape']} ({c['mb']} MB)"
                             for c in facts.get("whole_plane_copied", ())))
    if facts.get("state_plane_copies"):
        out.append(f"{what}: {facts['state_plane_copies']} copies or "
                   "transposes of a whole state plane")
    if facts.get("state_row_relayout_copies"):
        out.append(f"{what}: {facts['state_row_relayout_copies']} "
                   "layout-changing copies of a row of a state plane")
    for w in facts.get("state_writes") or ():
        if isinstance(w, dict) and w["on_minor_most"]:
            out.append(f"{what}: {w['count']} writes into a state plane "
                       "carry their traced index on the lane dimension")
    if facts["while_plane_copies"]:
        out.append(f"{what}: {facts['while_plane_copies']} whole planes "
                   "copied into a while loop or inside one")
    if facts["row_relayout_copies"]:
        out.append(f"{what}: {facts['row_relayout_copies']} layout-changing "
                   "copies of a cache row")
    return out


def described_generator(device):
    """The Generator, lowering for a described chip: every aval placed on
    ``device``, through the Generator's own ``slot_execs``.  Nothing can
    be placed on a described chip, so a relay is only noted, and a weight
    "lies" in its shape's default layout there."""
    import jax
    from jax.experimental.layout import Format
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.text import generation as G
    one = SingleDeviceSharding(device)

    def place(a):
        fmt = a.sharding if isinstance(a.sharding, Format) else None
        return G._aval(a, one if fmt is None else Format(fmt.layout, one))

    class Described(G.Generator):
        def _state_avals(self):
            return jax.tree_util.tree_map(place, super()._state_avals())

        def _lower(self, fn, arg_avals, jit_kw, free=False):
            return super()._lower(
                fn, jax.tree_util.tree_map(place, arg_avals), jit_kw, free)

        def _lower_data(self, fn, arg_avals, jit_kw):
            return super()._lower_data(
                fn, jax.tree_util.tree_map(place, arg_avals), jit_kw)

        def _held_layouts(self):
            return {(i, name): G._default_layout(a, device)
                    for i, tree in enumerate(self._state) if not i % 2
                    for name, a in tree.items()}

        def _place(self, formats):
            return sum(int(self._state[i][name].nbytes)
                       for i, name in formats)

    return Described


def main(argv):
    if len(argv) not in (1, 2):
        print(__doc__, file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    import importlib
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.nn.functional.attention import decode_block
    from paddle_tpu.ops.pallas import _mode
    # the programs are traced HERE, on the CPU, for the described chip:
    # what the code asks of the backend while it traces (the kernels'
    # gates, interpret or compile) is answered for that chip, so that
    # what is checked is what is served
    attention._on_tpu = lambda: True
    _mode.interpret = lambda: False
    with open(os.path.join(ROOT, "benchmark", "configs",
                           argv[0] + ".json")) as f:
        cfg = json.load(f)
    # the configuration names its model family, as for the runners
    family = importlib.import_module("benchmark.models." + cfg["family"])
    sv = cfg["serve"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    gen = described_generator(topo.devices[0])(
        family.build_unweighted(cfg), seq_buckets=sv["seq_buckets"],
        max_len=sv["max_len"])
    S, C, T = sv["slots"], sv["max_len"], sv["prefill_chunk"]
    if len(argv) > 1:
        S = int(argv[1])            # try another slot count
    planes = gen.slot_cache_avals_all(S, C)
    plane_shapes = {tuple(p.shape) for c in planes for p in c}
    # a layer without columns keeps a state (text/generation.py cache_spec)
    state_shapes = {tuple(p.shape)
                    for c, spec in zip(planes, gen.cache_spec(C))
                    if not spec["columns"] for p in c}
    n_state = len(jax.tree_util.tree_leaves(gen._state_avals()))
    progs = {"step": gen._step_program(S, C),
             "chunk": gen._chunk_program(S, T, C)}
    # the weights in their default layouts first: what each program alone
    # would do to them (nothing is settled by a bare lowering)
    default = {what: weight_copies(gen._lower(
        fn, avals, {"donate_argnums": donate}).as_text(), n_state)
        for what, (_key, _kind, fn, avals, _extra, donate) in progs.items()}
    served = dict(zip(progs, gen.slot_execs(S, T, C)))
    print(json.dumps({"config": cfg["name"], "weights": gen.weights_layout,
                      "plane_kinds": gen.plane_kinds()}), flush=True)
    faults = []
    for what, compiled in served.items():
        text = compiled.as_text()
        facts = inspect(text, plane_shapes, state_shapes)
        if what == "step" and "kv" in gen.plane_kinds():
            # the column blocks of the step's attention (cached_attention)
            # ... and whether each row reads its own (one kernel, no
            # while loop) or every row the span (``Generator.step_read``)
            facts = {"attn_block": decode_block(C),
                     "step_read": gen.step_read(C), **facts}
        # the form of a latent model's cached attention at this width
        form = gen.latent_form(1 if what == "step" else T)
        if form is not None:
            facts = {"latent_form": form, **facts}
        left = weight_copies(text, n_state)
        facts.update(
            weight_copies_default=len(default[what]),
            weight_copies_default_mb=round(
                sum(mb for _, mb in default[what]), 1),
            weight_copies=len(left),
            weight_copies_mb=round(sum(mb for _, mb in left), 1))
        facts["scope_instructions"], facts["unscoped_pct"] = \
            scope_coverage(text)
        print(json.dumps({"config": cfg["name"], "program": what,
                          "slots": S, "cache": C, **facts}), flush=True)
        mem = compiled.memory_analysis()
        print(json.dumps({"program": what, "memory_gib": {
            k: round(getattr(mem, k + "_size_in_bytes") / 2 ** 30, 3)
            for k in ("argument", "output", "alias", "temp",
                      "generated_code")}}), flush=True)
        faults += _faults(what, facts)
        # a weight the two disagreed on stays as it lies, and the program
        # that wanted it otherwise goes on copying it: no fault
        n_agreed = len(left) - gen.weights_layout["weights_layout_disagreed"]
        if n_agreed > 0:
            faults.append(f"{what}: at least {n_agreed} weights on which the "
                          "programs agreed are still copied in every run: "
                          + ", ".join(sorted({n for n, _ in left})[:4]))
    if sv.get("prefix_cache"):
        # the prefix cache's two data movers over the same planes: the push
        # writes a cached block into a row in place (planes aliased, the
        # traced row and column indices off the lanes, no plane copied),
        # the pull reads one out and copies no plane either
        from paddle_tpu.serving.prefix_cache import require_kv_planes
        require_kv_planes(gen.cache_spec(C), C)
        # what the budget holds beside the programs' arguments: a block is
        # one row x T columns of EVERY plane (a layer's selector keys
        # beside its latent rows)
        block = jax.tree_util.tree_leaves(gen._block_avals(S, T, C))
        block_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                          for a in block)
        budget = int(float(sv.get("prefix_cache_hbm_mb", 0)) * 2 ** 20)
        print(json.dumps({"config": cfg["name"], "prefix_cache": {
            "planes_per_block": len(block), "block_bytes": block_bytes,
            "budget_gib": round(budget / 2 ** 30, 3),
            "budget_blocks": budget // block_bytes}}), flush=True)
        for what, compiled in (
                ("kv_push_block", gen.push_block_exec(S, T, C)),
                ("kv_pull_block", gen.pull_block_exec(S, T, C))):
            facts = inspect(compiled.as_text(), plane_shapes, state_shapes)
            if what == "kv_pull_block":     # read-only: nothing to alias
                facts["planes_aliased"] = facts["planes_total"]
            print(json.dumps({"config": cfg["name"], "program": what,
                              "slots": S, "cache": C, "block": T, **facts}),
                  flush=True)
            faults += _faults(what, facts)
    # the row write that activates a row, lowered as the loop gets it
    vocab = gen._vocab_size()
    facts = logits_put(gen.put_logits_row_exec(S).as_text(), (S, vocab))
    print(json.dumps({"config": cfg["name"], "program": "put_logits_row",
                      "slots": S, "vocab": vocab, **facts}), flush=True)
    if not facts["logits_aliased"]:
        faults.append("put_logits_row: the [S, V] logits are copied, not "
                      "written in place")
    for f in faults:
        print("FAULT " + f, flush=True)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

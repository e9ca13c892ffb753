#!/usr/bin/env python
"""Generate docs/METRICS.md: the frozen inventory of the typed metrics
plane.

Same discipline as tools/gen_api_spec.py → API.spec: every Counter /
Gauge / Histogram registered in the default MetricsRegistry (after
importing the instrumented modules) is listed with its name, type,
labels and owning module; tests/test_metrics_plane.py regenerates the
inventory in-memory and fails when a published metric is missing from
the committed file.  Run

    python tools/gen_metrics_doc.py > docs/METRICS.md

to (re)freeze deliberately after adding a metric.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# modules that register typed metrics at import time — extend this list
# when a new subsystem publishes into the default registry
INSTRUMENTED_MODULES = [
    "paddle_tpu.serving.scheduler",
    "paddle_tpu.serving.prefix_cache",
    "paddle_tpu.serving.sessions",
    "paddle_tpu.serving.cluster.handoff",
    "paddle_tpu.serving.cluster.router",
    "paddle_tpu.serving.cluster.obs",
    "paddle_tpu.serving.cluster.lifecycle",
    "paddle_tpu.profiler.flight",
    "paddle_tpu.parallel.train_step",
    "paddle_tpu.rec.wide_deep",
    "paddle_tpu.text.speculative",
    "paddle_tpu.jit.persistent_cache",
    "paddle_tpu.nn.layer.moe",
]

HEADER = """\
# METRICS — typed metrics plane inventory

Generated by `python tools/gen_metrics_doc.py > docs/METRICS.md`; do not
edit by hand.  `tests/test_metrics_plane.py` regenerates this inventory
and fails when a registered metric is missing, so adding a Counter /
Gauge / Histogram without re-freezing the doc breaks CI (the
`gen_api_spec` discipline applied to observability).

Every family lives in `profiler.metrics.default_registry()`, mirrors
into the legacy `utils.monitor` stat registry under a flattened name,
and is exposed in Prometheus text format via `tools/serve.py
--metrics-port`, `profiler.metrics.serve_metrics()`, or
`profiler.metrics.write_textfile()`.  Legacy monitor.h-style int64
gauges (`jit_compile_count`, `serving_queue_depth`, ...) ride the same
exposition as the `paddle_tpu_stat{name=...}` family.
"""

TRAILER = """\

## Device time by program and by named scope

Not part of the metrics plane above: what a profiler capture (`.xplane.pb`)
can say about the device's BUSY seconds.  The model code names its work with
`jax.named_scope` (metadata written when a program is traced; nothing at run
time), from one small vocabulary.  A name is a kind of work, never a layer
index: 48 layers of `attention` add up.

| scope | where it is entered | covers |
|---|---|---|
| `embed` | `GPTModel.forward` / `forward_cached`, `BertModel.forward`, the latent and hybrid decoders | token, position and type lookups, the mask arithmetic beside them |
| `attention` | `TransformerEncoderLayer.forward`, `LatentDecoderLayer`, `HybridDecoderLayer` (an attention layer) | a layer's attention half: its norm, the q/k/v/out products, the scores, the residual add.  Inside it the newer families keep `latent_attention`, `latent_attention/per_head` (the per-head form of a wide block of queries: an XLA `while` loop over the column blocks, or, where the chunk takes the kernel, ONE custom call `latent_attention_per_head` a layer, under the same path), `selector` (the learned column selector's projections and key write; inside it `selector/score`, the scores of every valid column, apart from `selector/select`, the radix search among them: one conditional with a branch for each width the search may take, `select/cond/branch_<i>_fun`, `branch_0_fun` for a dispatch in which nothing is to be decided), `gqa_attention` |
| `attention/fused` | `nn.functional.attention` (`attention_bse`, `attention_bnsh`), around the call of a Pallas attention kernel | an un-cached call site that took a kernel: its custom calls by name, `attention_single_block_fwd` and, under `transpose(jvp(..))`, `attention_single_block_bwd` (the blocked form's three have no name of their own).  Bucket `attention`, inside a layer's or alone |
| `cache_write` | `ring_block_write`, the short convolution's state, `Generator`'s `_slice_row` / `_splice_row` (`cache_write/row_slice`, `cache_write/row_splice`) | every write into a cache plane, and, for a chunk whose `chunk_row` is `"sliced"`, its batch-1 row out of the planes and back (an `"in_place"` chunk has only `ring_block_write`'s block at `(row, 0, pos, 0)` here; its read of the row is the `attention` product's operand).  Innermost wins: a plane write inside `attention` counts here |
| `mlp` | `TransformerEncoderLayer.forward`, `SwiGLU.forward`, the decoders' dense layers | a layer's dense feed-forward half with its norm and residual add; a shared expert |
| `experts` | `DroplessMoE.forward`, the decoders' expert layers; the router as `experts/router` | routed experts with their norm and residual add |
| `short_conv` | `ShortConv.forward_cached`, `HybridDecoderLayer` (a convolution layer) | the gated short convolution with its norm and residual add |
| `state_space` | `HybridDecoderLayer` (a state-space layer); inside it `Mamba2Mixer` enters `state_space/conv`, `state_space/scan` (a block of several tokens: the chunked scan) and `state_space/update` (one token: the step's update) | a Mamba-2 layer with its norm, projections, gate, group norm and residual add; `conv` the causal convolution over the block and the inputs kept before it, `scan` / `update` the recurrence itself.  `benchmark/scope_buckets.json` lists no such component, so the accepted `device_scoped_pct.*` counts these layers as unscoped; `benchmark/layer_metrics/_state_space_scope.py` reads them by reducing the capture once more with these four components listed (`step_state_space_ms`, `chunk_state_space_ms`, `state_update_roofline_pct`, `ssm_scan_roofline_pct`) |
| `linear_attention` (inside `attention`) | `LightningAttention._mix` around the recurrence; inside it `mamba2.state_mix` enters `linear_attention/scan` (a block of several tokens: the chunked scan) and `linear_attention/update` (one token: the step's update) | the float32 matrix state's recurrence alone (`S_t = exp(-s_h) S_{t-1} + k_t^T v_t`, read through `q`); the layer's projections, norms, positions and gate lie directly under `attention`, where the accepted `step_attention_ms.*` / `chunk_attention_ms.*` count the whole layer.  `benchmark/layer_metrics/_sala_scope.py` reads the component and its parts by reducing the capture once more with them listed (`step_linear_attention_ms`, `chunk_linear_attention_ms`; `linear_update_roofline_pct` and `linear_scan_roofline_pct` take the time under `update` / `scan` plus the compiler's own copies of a whole state plane, `linear_attention/plane_copy` in the reader's printed table: the step's update writes each new plane into the chip's other memory space and a copy that carries no scope takes it to the output; no other op without a scope is counted) |
| `linear_attention/scan/solve`, `linear_attention/scan/carry` (inside `attention`) | `nn/layer/gated_delta.py::delta_scan`, under the SAME `linear_attention/scan` that a lightning layer's scan lies under (and `delta_update` under `linear_attention/update`: `GatedDeltaNet._mix` enters `linear_attention`, `delta_mix` the part) | a gated delta-rule layer's chunked scan in its two parts: `solve`, the triangular system that gives a scan chunk's pseudo-values (the scores `K K^T`, `unit_lower_inverse`, its two right-hand sides), the part no other linear layer has; `carry`, the chunks' own sums and the state passed from one to the next.  The accepted `_sala_scope.py` lists neither part, so its readers go on counting both under `linear_attention/scan` (`chunk_linear_attention_ms`, `linear_scan_roofline_pct`, with this family's operations and bytes from `benchmark/counts/gigachat3_5.py`); `benchmark/layer_metrics/_delta_scope.py` reduces the capture once more with the two listed (`chunk_delta_solve_ms`).  The layer's convolution lies under `attention/conv`, its projections, norm and gate directly under `attention` |
| `sparse_attention` (inside `attention`) | `BlockSparseAttention.forward_cached`: `sparse_attention/pool` (`pool_keys_write`: the pooled keys that the block completes, written under each row's mask), `sparse_attention/select` (`choose_blocks`: the pooled scores, the blocks' scores, the choice), `sparse_attention/read` (the attention itself, in both programs: `span_attention` over every valid column of the span under `block_keep`'s mask of the chosen blocks; the tree holds no gathered form, PERF.md section 6, PR 44) | a block-sparse layer's own work beside its projections; `_sala_scope.py` reads it (`step_sparse_attention_ms`, `chunk_sparse_attention_ms`; `sparse_read_roofline_pct` = the chosen blocks' and pooled entries' least bytes over the step's time under `select` + `read`) |
| `head` | the logits product of every model, BERT's pooler and heads; `head/sample` in `Generator._build_step` | vocabulary product; the step's `argmax` / `where` |
| `loss`, `optimizer`, `cast_params` | `TrainStep._loss_of` and the models' own loss, the optimizer update of `TrainStep._build_step`, `TrainStep._cast_compute` | the training step's own parts; backward is told from forward by the `transpose(jvp(..))` wrapper JAX writes itself |

`paddle_tpu.profiler.ledger.program_scopes()` returns `{XLA module name:
{instruction name: {"scope": path[, "backward": True][, "inherited":
True]}}}` for every program the compile ledger recorded with its text
(`Generator`'s executables, `TrainStep`'s step): the path is read off the
compiled program's own text (`metadata={op_name=...}`), wrappers and the
trailing primitive stripped.  An instruction the compiler made (no
`op_name`: a weight's prefetch, a relayout) takes the entry of the first
instruction that uses its result, marked `inherited`.  Nothing is fetched or
parsed until the function is called; an executable is held weakly.  A
capture names each op event by the same instruction name (`%fusion.555 =
...`), so the two join: `benchmark/layer_metrics/_program_scopes.py` lays
every op under the program run that contains it, gives it its self time
(a `while` holds its body), and adds the seconds up by the bucket
(`benchmark/scope_buckets.json`) of the innermost listed component of its
path; no path is `unscoped`, a name the table lacks `unknown_instruction`.

Which form an un-cached attention call site takes is decided while its
program is traced, from the site's shapes (`ops/pallas/flash_attention.py`
`fused_form`), so the count is a fact of the program: a `train_step`
compile event of the ledger carries `attention_form: {"fused": n, "xla":
m}`, the step's call sites by form (`count_attention_forms`; the BERT-large
step of the benchmark reads `{"fused": 24, "xla": 0}` on the chip, `{"fused":
0, "xla": 24}` on the CPU).

Which form a latent model's cached attention takes is decided the same way,
from the layer's own dimensions and the width of the query block a program
is traced for (`LatentAttention.cached_form`): `"absorbed"` for a step's one
query a row and any narrow block; for a wide block (a prefill chunk) the
per-head form, as `"per_head"`, an XLA loop over the column blocks, or as
`"per_head_fused"`, ONE Pallas kernel over the row's live column blocks with
the expanded keys and values, the float32 scores and the running sums in
VMEM (`ops/pallas/latent_attention.py`; taken on a TPU, without a mesh of
several devices, where the query block and `attn_block` are whole multiples
of 128, the widths lie on the lane grid and the measured table beside
`fused_latent_form` says it is the faster); `"mixed"` where a model's layers
decide differently.  `latent_form` is a fact of the programs: in the
`extra` of the `generate_step` / `generate_chunk` compile events, in
`SlotLoop.stats()["latent_form"]` as `{"step": .., "chunk": ..}`, in
`tools/kv_layout_check.py`'s line for each program, and in the persistent
executable cache's identity (an executable stored under another choice is
not loaded).

How a prefill chunk reaches its joining row is decided when the chunk
program is traced, from what the model declares (`Generator.chunk_row`):
`"in_place"` for a model whose cached forward takes the row
(`cached_forward_takes_row`: the GPT family, uniform K/V ring planes): the
`[1, G, T, L]` block is written into the full donated `[S, G, C, L]` plane
at `(row, 0, pos, 0)` and the batch-1 queries attend that row of the plane
where it lies; `"sliced"` for every other model: the row is cut out of
every plane, run as a batch-1 cache and spliced back whole.  `chunk_row` is
a fact of the program, set once and not reset with the counters: in the
`extra` of the `generate_chunk` compile event, in
`SlotLoop.stats()["chunk_row"]` beside `kv_heads_per_lane_row`, in the
persistent executable cache's identity where it is `"in_place"`, and
`tools/kv_layout_check.py`'s chunk line counts the whole rows a program cuts
or writes back (`row_sized_slices`: 0 in place, 2 a plane sliced).  No
metric of its own: `chunk_cache_write_ms.*` reads what it costs.

Which form a decode step's one-query read of plain K/V ring planes takes is
decided when the step program is traced, from what the code can see
(`nn.functional.attention.decode_read_form`): `"per_row"`, ONE Pallas
kernel that reads, of each generating row, the column blocks from its own
`start` to the frontier and nothing of a row that generates nothing
(`ops/pallas/span_decode.py`; taken on a TPU, without a mesh of several
devices, for bf16 / f32 planes of whole lane rows whose blocks start on a
tile's edge, where the layer passes no mask of chosen blocks), or `"span"`,
two XLA loops in which every row reads the blocks from the oldest generating
row's `start` to the frontier (the CPU, a mesh, a `keep` mask).  `step_read`
is a fact of the program (`Generator.step_read`; None for a model whose
column planes are not all of kind `kv`): in the `extra` of the
`generate_step` compile event, in `SlotLoop.stats()["step_read"]` beside
`chunk_row`, in `tools/kv_layout_check.py`'s step line, and in the
persistent executable cache's identity where it is `"per_row"`.  The loop's
`attn_blocks_read` / `attn_blocks_total` follow it, by host arithmetic on
what each step was handed: under `"per_row"` the sum over the generating
rows of `pos // block + 1 - start // block`, of `slots x blocks`; under
`"span"` the union span, of the plane's blocks.  Either way
`attn_span_read_pct.*` is the share of the planes' blocks a step streamed.

How wide the column selector's search runs is decided in the program, from
what a dispatch can see: it goes over the narrowest of a short list of
static widths (`nn.functional.attention.search_widths`: `2 k`, `4 k`, ...
below the plane's columns, then the plane) that holds the dispatch's widest
context, `last column - lowest start + 1`, and over nothing (no scores
either) while that context is no more than `index_topk`, where every valid
column is chosen.  The widths are a fact of the programs: the
`generate_step` / `generate_chunk` compile events of the ledger carry
`selector_widths` beside `latent_form` (a model in which no layer selects
has no such fact), and a layer's `cache_spec` hands them out as `select_widths`.
`SlotLoop.counters` says how often and how far the rule engaged, by the same
function the program calls (`searched_columns`), summed over the selecting
layers, committed with `steps` and zeroed by `reset_stats()`:
`selector_columns_searched` / `selector_columns_plane` over the steps (the
columns searched, of those the planes have), `chunk_selector_columns_searched`
/ `chunk_selector_columns_plane` over the prefill chunks.  Their ratio is 0
for a run whose contexts never pass `index_topk` and 1 for one whose every
dispatch needs the whole plane; only a loop over a model with a selecting
layer has them.

A loop over a model that keeps a SUMMED state (a layer whose `cache_spec`
kind is `ssm_state`: a state-space layer's float32 state, which stands for
every earlier token of its row) counts, beside `state_rows_held`, what fed
those states, by host arithmetic on the dispatch's `pos` and `start`
(`_tally_columns`), committed with `steps` and zeroed by `reset_stats()`:
`ssm_rows_updated`, the live rows of the steps (each updates its state once
a state-space layer: rows, not rows x layers), and `chunk_ssm_tokens`, the
valid tokens the prefill chunks scanned into a state (left padding passes
the state through and is not counted).  `state_update_roofline_pct` reads
the first against the device time under `state_space/update`,
`ssm_scan_roofline_pct` the second against `state_space/scan`, each with
the family's least bytes and operations from `benchmark/counts/`.

A linear-attention layer's matrix state is such a summed state too (kind
`ssm_state`: the same rule and the same two counters;
`linear_update_roofline_pct` and `linear_scan_roofline_pct` read them
against `linear_attention/update` and `linear_attention/scan`, each with
the compiler's own copies of a whole state plane).  A loop over
a model with a layer that chooses BLOCKS of its K/V columns from scores over
a pooled-key plane (`cache_spec` `select_blocks`, kind `kv+pooled_key`;
`stats()["plane_kinds"]` lists it) counts, by the same host arithmetic, a
layer and a live token: `sparse_blocks_valid` (the blocks its context has),
`sparse_blocks_selected` (those it reads: `top` of them past `dense_len`,
all of them inside it) and `pooled_entries_scored` (the pooled windows that
lie whole inside its context, past `dense_len`; none inside it), over the
steps' live rows, and their `chunk_` twins over the chunks' valid tokens.
`sparse_blocks_selected_pct` is the first two's ratio;
`sparse_read_roofline_pct` takes the selected blocks and the scored entries
a step.  Such a loop counts `kv_columns_valid` like a plain K/V model's, and
no `attn_blocks_*`: those count the column blocks of a span over plain K/V
planes alone (kind `kv`), and this loop's span is read under the chosen
blocks' mask (what it keeps of the span is in `sparse_blocks_*`).  A loop
over a model that keeps ONE kind of plane with columns beside states without
(a latent plane without selector or window beside `ssm_state` layers:
`stats()["plane_kinds"]` `["latent", "ssm_state"]`) counts the latent plane's
valid columns under `kv_columns_valid` too, once a dispatch, beside the
latent family's own `attn_columns_*` a layer; a model of latent planes ALONE
counts the latter only.

The plain slot loop keeps ONE decode step in flight: it dispatches step k+1
and only then reads step k's tokens (`paddle_tpu/serving/slots.py`, "One step
in flight"), so the device holds its next programs while the driver thread
emits, commits and retires.  `slot_loop::step_fetch`
(`stats()["phase_s"]["step_fetch"]`) is therefore the wait for the step
BEFORE the one just dispatched.  Two of `SlotLoop.counters` say what that
costs and whether it works, committed with `steps` and zeroed by
`reset_stats()` (a speculative loop, which reads every step before the
next, has neither):

| counter | counts | read beside |
|---|---|---|
| `steps_read_ready` | the steps whose read-back waited less than `slots.READ_READY_S` (0.5 ms): the tokens were on the host's side before the driver asked, so the driver's own work had outlasted the device's step and the device was living on the one queued step.  Near 0 where the device is the bottleneck (the read waits for a step that is still running, with the next queued behind it), near `steps` where the host is | `steps`; the seconds waited are `phase_s["step_fetch"]`, the device's side `device_idle_pct.*` |
| `slot_steps_retire_lag` | the slot-steps of rows a step passed by because they had taken the end token at the step before, which the host had not read when it dispatched: they count with the empty slots (`slot_steps_no_demand` / `slot_steps_drain_blocked`), not with `slot_steps_emitting`.  A row that ends by `max_new` costs none: the host knows at dispatch, and frees the slot in the same iteration as a loop that reads first | `steps x slots` |

To read a capture of your own: `paddle_tpu.profiler.Profiler` writes
`program_scopes.json` into `profiler_result_dir` when a record window
ends, beside the `.xplane.pb`, so both survive the process; then

    python3 -m benchmark.layer_metrics._program_scopes <trace dir>

prints, per program, `device seconds by scope, jit_step: {runs, mean_ms,
buckets {...}, unscoped, unknown_instruction, backward {...}}` and the ten
heaviest ops by self time with their scope.  `tools/kv_layout_check.py
<config>` prints `scope_instructions` and `unscoped_pct` of the served
programs compiled for a described chip: the coverage, before chip time.

Host spans that no benchmark metric reads, for an operator's own capture
(`profiler.span`, on their thread's line of any `.xplane.pb`):
`executor::{data_feed, compile, device_execute, fetch, dataset_upload,
dataset_scan}` (`static/executor.py`), `jit::{trace_compile, execute}`
(`jit/__init__.py`), `train_step::{data_feed, compile, dispatch,
collective_assemble}` (`parallel/train_step.py`).
"""


def render() -> str:
    import importlib
    for modname in INSTRUMENTED_MODULES:
        importlib.import_module(modname)
    from paddle_tpu.profiler.metrics import default_registry
    rows = default_registry().describe()
    lines = [HEADER]
    lines.append("| name | type | labels | module |")
    lines.append("|---|---|---|---|")
    for r in rows:
        labels = ", ".join(r["labels"]) or "—"
        lines.append(f"| `{r['name']}` | {r['type']} | {labels} | "
                     f"`{r['module']}` |")
    lines.append("")
    for r in rows:
        lines.append(f"- **`{r['name']}`** ({r['type']}): {r['doc']}")
    return "\n".join(lines) + "\n" + TRAILER


def main():
    sys.stdout.write(render())


if __name__ == "__main__":
    main()

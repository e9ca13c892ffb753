#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json``) under a traffic mix
(``benchmark/traffic/<traffic>.json``).  The configuration names its runner
(``benchmark/runners/<runner>.py``) and model family; the traffic names its
kind (``benchmark/generators/<kind>.py``); every per-layer metric has a
reader (``benchmark/layer_metrics/<name before the first dot>.py``).  No
cell, configuration, mix or metric is named in any ``.py`` file: a later PR
adds files and ``BENCHMARK.json`` entries and edits nothing.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``).  The run fails, with no result line, when JAX finds no TPU
or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()          # set-up is counted from here

import argparse                              # noqa: E402
import importlib                             # noqa: E402
import json                                  # noqa: E402
import os                                    # noqa: E402
import sys                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT, bench: dict = None) -> dict:
    """Everything one run needs, found by the names in ``BENCHMARK.json``."""
    bench = bench or _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    bdir = os.path.join(root, bench["paths"][0])
    config = _load_json(os.path.join(root, entry["file"]))
    traffic = _load_json(os.path.join(bdir, "traffic",
                                      cell["traffic"] + ".json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": int(cell["chips"]), "config": config,
        "traffic": traffic, "bench_dir": bdir,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "peaks_file": os.path.join(bdir, "peaks.json"),
    }


def require_chips(n: int) -> dict:
    """The device record, or exit non-zero: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"benchmark: need {n} TPU chip(s), JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    return device_record()


def device_record() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache where ``utils/cache_dirs.py`` puts
    it (a fixed path inside the checkout, or ``JAX_COMPILATION_CACHE_DIR``),
    for every program however small: nothing compiles twice in a checkout."""
    import jax
    from paddle_tpu.utils import cache_dirs
    cache_dirs.enable_jax_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module(
            "benchmark.layer_metrics." + m["name"].split(".")[0])
        value = reader.compute(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, bench: dict = None, config: dict = None,
             traffic: dict = None, check_device: bool = True,
             t_start: float = None) -> dict:
    """Run one cell and return the result object.  ``bench``, ``config``
    and ``traffic`` replace what the files say and ``check_device=False``
    skips the look for a chip: the tests' way to a tiny size on the CPU."""
    cell = load_cell(workload, root, bench)
    if config is not None:
        cell["config"] = config
    if traffic is not None:
        cell["traffic"] = traffic
    if check_device:
        require_chips(cell["chips"])
    enable_compile_cache()
    runner = importlib.import_module(
        "benchmark.runners." + cell["config"]["runner"])
    t0 = T_PROCESS_START if t_start is None else t_start
    res = runner.run(cell, int(seed), float(seconds), bool(trace), t0)

    device = device_record()
    # the peak as JAX reports it, or what the chip held when the window
    # closed, temporaries included, whichever is larger
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      int(res.get("memory_at_close_bytes", 0)))
    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if trace:
        from benchmark import trace_reduce
        ctx = dict(res["ctx"])
        ctx["cell"] = cell
        ctx["peaks"] = _load_json(cell["peaks_file"]).get(device["kind"])
        ctx["trace"] = trace_reduce.reduce(res["trace_dir"],
                                           res.get("span_names", ()))
        out["metrics"] = layer_metrics(cell, ctx)
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            out["breakdown"] = {"device_ops": ctx["trace"]["top_ops"],
                                "idle_gaps": ctx["trace"]["idle_gaps"]}
    else:
        out["metrics"] = {
            m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in cell["end_to_end"]}
    out["device"] = device
    out["checks"] = res["checks"]
    out["reference_s"] = res.get("reference_s")
    out["setup_phases_s"] = res.get("setup_phases_s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # a served cell leaves daemon threads decoding the rows that were in
    # flight when its window closed; end the process without waiting on them
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

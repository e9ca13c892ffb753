"""Wide-mesh subprocess smokes for the HLO audit (slow-marked: each
subprocess provisions a 16-device virtual CPU platform and pays several
XLA compiles — the repo convention for anything tier-1 must not pay).

Covers the pod-scale surface the in-process tests cannot (tier-1 runs on
an 8-device platform): the CLI over a 16-device mesh in strict mode, the
seeded negative exit code, and the dryrun phase-5 worker (scaling rows +
seeded gate + pp mix + ledger cross-link at width 16).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wide_env(n):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform"))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    return env


@pytest.mark.slow
def test_cli_zoo_wide_mesh_strict_clean():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hlo_audit.py"),
         "--zoo", "--mesh", "8x2", "--strict", "--json"],
        capture_output=True, text=True, timeout=840, env=_wide_env(16),
        cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    payload = json.loads(p.stdout)
    assert payload["n_errors"] == 0
    models = {r["model"] for r in payload["results"]}
    assert models == {"lenet", "resnet_block", "bert", "gpt", "gpt_moe",
                      "wide_deep"}
    for r in payload["results"]:
        assert r["ok"] and r["mesh"] == "dp8xmp2"
        assert r["stats"]["collective_count"] > 0
        assert r["stats"]["memory"]["peak_bytes"] > 0
    # the sharded-embedding CTR step must carry the all-to-all routing
    # pattern the transformer zoo never produces (ISSUE 10)
    wd = [r for r in payload["results"] if r["model"] == "wide_deep"][0]
    assert wd["stats"]["collectives"]["all-to-all"]["count"] > 0
    # the expert-parallel MoE step routes tokens over EP=DP here
    # (ISSUE 14): the token all_to_alls must survive compilation
    moe = [r for r in payload["results"] if r["model"] == "gpt_moe"][0]
    assert moe["stats"]["collectives"]["all-to-all"]["count"] >= 4
    # every lowering ledgered once with its mesh label (the
    # zero-steady-state-recompile convention extended to audit runs)
    assert len(payload["ledger"]) == 6
    assert all("arg:mesh" in e["key"] and "dp8xmp2" in e["key"]
               for e in payload["ledger"])


@pytest.mark.slow
def test_cli_seeded_wide_mesh_exits_nonzero():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hlo_audit.py"),
         "--seeded", "--mesh", "8x2", "--strict"],
        capture_output=True, text=True, timeout=600, env=_wide_env(16),
        cwd=REPO)
    assert p.returncode == 1, (p.stdout[-1500:], p.stderr[-1500:])
    assert "hlo-full-gather" in p.stdout
    # both negative fixtures must fire: the de-sharded ZeRO state AND the
    # de-sharded annotated embedding table (ISSUE 10 annotation contract)
    assert "seeded_desharded_zero" in p.stdout
    assert "seeded_desharded_table" in p.stdout


@pytest.mark.slow
def test_cli_gpt_moe_expert_mesh_strict_clean():
    """ISSUE 14: the gpt_moe builder over a dedicated 16-wide expert-
    parallel mesh (named-axis spec 'ep16') audits clean in strict mode
    and the compiled step carries the token-routing all_to_alls."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hlo_audit.py"),
         "--model", "gpt_moe", "--mesh", "ep16", "--strict", "--json"],
        capture_output=True, text=True, timeout=840, env=_wide_env(16),
        cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    payload = json.loads(p.stdout)
    assert payload["n_errors"] == 0
    (r,) = payload["results"]
    assert r["model"] == "gpt_moe" and r["ok"] and r["mesh"] == "ep16"
    assert r["stats"]["collectives"]["all-to-all"]["count"] == 4
    assert len(payload["ledger"]) == 1
    assert "arg:mesh" in payload["ledger"][0]["key"]


@pytest.mark.slow
def test_dryrun_phase5_worker_width16():
    """One width of the dryrun's phase 5 end-to-end: all mesh mixes
    (dp×mp×sp z1, dp×mp z3, pure-dp resnet, pp×dp pipeline, plus the
    FLAGS_autoshard=apply rules-sharded GPT) audit clean, the seeded
    de-sharded fixture fails at ERROR, and the rows carry the
    scaling-table fields."""
    code = "import __graft_entry__ as g; g._hlo_audit_impl(16)"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=840, env=_wide_env(16), cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "seeded de-sharded-ZeRO fixture flagged at ERROR" in p.stdout
    assert "seeded de-sharded-table fixture flagged at ERROR" in p.stdout
    rows = None
    for ln in p.stdout.splitlines():
        if ln.startswith("HLO_AUDIT_ROWS "):
            rows = json.loads(ln[len("HLO_AUDIT_ROWS "):])
    assert rows is not None
    cfgs = {r["config"] for r in rows}
    assert cfgs == {"bert_z1_dp_mp_sp", "bert_z3_dp_mp",
                    "resnet18_z1_dp", "bert_pp2_dp",
                    "gpt_autoshard_dp_mp", "wide_deep_sharded_emb",
                    "gpt_moe_ep"}
    # the sharded-embedding config must carry all-to-all traffic
    wd = [r for r in rows if r["config"] == "wide_deep_sharded_emb"][0]
    assert wd["collectives"]["all-to-all"]["count"] > 0
    # the MoE config: 4 all_to_alls in the train step (2 fwd + 2
    # transposed bwd for its one MoE block), and the forward-census
    # exactly-two-per-block assert printed its line (ISSUE 14)
    moe = [r for r in rows if r["config"] == "gpt_moe_ep"][0]
    assert moe["mesh"] == "ep16"
    assert moe["collectives"]["all-to-all"]["count"] == 4
    assert "gpt_moe_ep forward census 2 all-to-alls == 2 x 1 MoE " \
        "block(s)" in p.stdout
    for r in rows:
        assert r["n_devices"] == 16
        for field in ("collective_count", "collective_wire_bytes",
                      "flops", "memory", "mesh", "zero"):
            assert field in r, (field, r)

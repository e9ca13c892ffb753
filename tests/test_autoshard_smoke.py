"""Subprocess smokes for tools/autoshard.py (slow-marked: each run
provisions a 16-device virtual CPU platform and pays the AOT compiles of
four zoo train steps — the repo convention for anything tier-1 must not
pay).

The CI lane the satellite asks for: ``--zoo --apply --strict`` must exit
0 with every model rule-sharded and HLO-audit-clean on a wide mesh, and
the ``--seeded`` contradicting-hand-annotation fixture must exit 1 —
the conflict gate is proven to fire, not merely to pass clean tables.
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
def test_cli_zoo_apply_strict_wide_mesh_clean():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "autoshard.py"),
         "--zoo", "--mesh", "8x2", "--apply", "--strict", "--json"],
        capture_output=True, text=True, timeout=840, env=_wide_env(16),
        cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    payload = json.loads(p.stdout)
    assert payload["n_conflicts"] == 0
    assert payload["n_unmatched"] == 0
    assert payload["n_audit_errors"] == 0
    models = {r["model"] for r in payload["results"]}
    assert models == {"bert", "gpt", "resnet_block", "wide_deep"}
    for r in payload["results"]:
        assert r["applied"] and r["mesh"] == "dp8xmp2"
        assert r["audit"]["ok"], r["model"]
        assert r["plan"]["n_sharded"] > 0, r["model"]
        assert r["plan"]["n_unmatched"] == 0, r["model"]
        # every sharded leaf carries rule provenance
        for e in r["plan"]["entries"]:
            if e["status"] == "matched":
                assert e["rule"] and e["table"], e


@pytest.mark.slow
def test_cli_seeded_conflict_exits_nonzero():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "autoshard.py"),
         "--seeded", "--mesh", "4x2", "--strict", "--json"],
        capture_output=True, text=True, timeout=600, env=_wide_env(8),
        cwd=REPO)
    assert p.returncode == 1, (p.stdout[-1500:], p.stderr[-1500:])
    payload = json.loads(p.stdout)
    assert payload["n_conflicts"] >= 1
    seeded = [r for r in payload["results"]
              if r["model"] == "seeded_conflicting_annotation"]
    assert seeded and seeded[0]["plan"]["n_conflicts"] == 1
    bad = [e for e in seeded[0]["plan"]["entries"] if e["conflict"]]
    assert bad[0]["rule"] == "tp-qkv-column"

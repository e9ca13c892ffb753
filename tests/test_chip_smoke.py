"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide §2.1-2.2): its
phases in-process at ``TINY``, the four-chip comparison on four of the
eight virtual CPU devices, the refusal to run without a TPU, the compile
cache placement rule, and the one-process-per-chip rule for every parent
that starts children which need the chip."""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from paddle_tpu.utils import cache_dirs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def no_native_build(monkeypatch):
    """The chip machine gets only committed files and need not have g++:
    with paddle_tpu/native's compiled-on-first-use library unavailable
    (``load()`` returns None, a supported state) the phases still pass."""
    from paddle_tpu import native

    def no_toolchain(*a, **kw):
        raise OSError("g++: not found")
    monkeypatch.setattr(native, "_build", no_toolchain)
    monkeypatch.setattr(native, "_LIBS", {})
    assert native.load() is None


def test_train_phase_tiny(no_native_build):
    rec = chip_smoke.phase_train(chip_smoke.TINY)
    assert rec["phase"] == "train" and rec["checked"]["steps"] >= 5
    assert rec["checked"]["loss_last"] < rec["checked"]["loss_first"]


def test_serve_then_cache_phase_tiny(no_native_build):
    """Both decode runtimes against generate(), then a second boot that
    loads every executable the first one stored."""
    served = chip_smoke.phase_serve(chip_smoke.TINY)
    runs = served["record"]["checked"]["runs"]
    assert set(runs) == {"decode_slots=0",
                         f"decode_slots={chip_smoke.TINY.slots}"}
    for run in runs.values():       # f32-exact on the CPU: no near-tie exit
        assert run["positions_equal"] == run["positions"]
    rec = chip_smoke.phase_cache(chip_smoke.TINY, served)
    assert rec["checked"]["fresh_compiles"] == 0
    assert rec["checked"]["executables_loaded"] == sum(
        r["warmup_compiles"] for r in runs.values())


def test_layouts_phase_tiny(no_native_build, weight_wishes):
    """The relaid-weights comparison: the pass-through (the CPU compiler
    wants every weight as it lies), then with the free compile made to ask
    for transposed projections, where it compares two unlike programs."""
    rec = chip_smoke.phase_layouts(chip_smoke.TINY)
    assert rec["checked"]["weights_relaid"] == 0
    assert rec["checked"]["step_logits_bit_equal"]
    weight_wishes(lambda prog, name: name.endswith("_proj.weight"))
    rec = chip_smoke.phase_layouts(chip_smoke.TINY)
    layers = chip_smoke.TINY.gpt.num_layers
    assert rec["checked"]["weights_relaid"] == 4 * layers
    assert rec["checked"]["chunk_logits_bit_equal"]


def test_hybrid_phase_tiny(no_native_build):
    """Conv states beside K/V planes: chunks + 32 steps and a slot loop
    with reused slots and waiting rows, against the plain reference."""
    rec = chip_smoke.phase_hybrid(chip_smoke.TINY)["checked"]
    assert rec["logits_rel_worst"] < 1e-4 and rec["served_gap_widest"] < 1e-4
    assert rec["state_rows_held"] > 0 and rec["steps"] == 32


def test_latent_forms_phase_tiny(no_native_build):
    """A kimi-shaped and a dots3-shaped full layer, chunks of 32 over a
    context of 96 in all three cached forms (the kernel interpreted, at
    widths the chip's compiler would refuse): float32 here, so they agree
    to summation order.  The layer's own rule keeps the XLA loop: this is
    the CPU, and the widths are off the lane grid."""
    rec = chip_smoke.phase_latent_forms(chip_smoke.TINY)["checked"]
    assert set(rec) == {name for name, _, _ in chip_smoke.LATENT_LAYERS}
    for got in rec.values():
        assert got["rel_worst"] < 1e-5 and got["rule"] == "per_head"
        assert got["row_flips"] == 0.0
        assert set(got["chunk_ms"]) == {"absorbed", "per_head",
                                        "per_head_fused"}
    assert [got["selects"] for got in rec.values()] == [False, True]


def test_gated_delta_phase_tiny(no_native_build):
    """The delta rule alone at the tiny widths: one 16-token chunk of one
    row at scan chunks of 4 and 8 with the triangular system solved in all
    three forms, each held to the token-by-token recurrence (float32 here,
    so they agree to summation order), and one step of the 3 slots; the CPU
    has no entry in the peaks' table, so no roofline share is read."""
    rec = chip_smoke.phase_gated_delta(chip_smoke.TINY)["checked"]
    assert set(rec["scan_ms"]) == {f"{f}/{w}" for w in (4, 8) for f in (
        "halves", "product", "substitution")}
    assert max(rec["scan_rel_worst"].values()) < 1e-5
    assert rec["kept"] == "halves/8" and rec["rows"] == 3
    assert "update_ms" in rec       # (a time on the CPU says nothing)
    assert rec["roofline_pct"] == {"update": None, "scan": None}


def test_kernels_phase_tiny_interprets_on_cpu(no_native_build):
    rec = chip_smoke.phase_kernels(chip_smoke.TINY)
    assert rec["checked"]["compiled_not_interpreted"] is False
    assert set(chip_smoke.KERNEL_TOL) <= set(rec["checked"])


def test_multichip_phases_on_four_virtual_devices():
    devices = jax.devices()[:4]
    rec = chip_smoke.phase_multichip_train(chip_smoke.TINY, devices)
    assert rec["checked"]["devices"] == 4
    assert rec["checked"]["params_sharded"] > 0
    rec = chip_smoke.phase_multichip_moe(chip_smoke.TINY, devices)
    assert rec["checked"]["expert_stack_devices"] == 4


def test_token_check_fails_on_a_wide_margin():
    """The near-tie exit is not a loophole: a wrong token where the
    reference is confident raises."""
    model = chip_smoke._gpt(chip_smoke.TINY, 0)
    prompt = chip_smoke._prompts(chip_smoke.TINY, 1)[1]
    want = [int(chip_smoke._plain_logits(model, prompt).argmax())]
    assert chip_smoke._tokens_agree(model, prompt, want, want, 0.0) == 1
    with pytest.raises(AssertionError, match="top-2 margin"):
        chip_smoke._tokens_agree(model, prompt, want, [want[0] + 1], 0.0)


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "no phase was run" in p.stderr
    assert '"ok"' not in p.stdout and '"phase"' not in p.stdout


def test_cache_dirs_follow_the_environment(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_dirs.jax_compile_cache_dir() == \
        os.path.join(REPO, ".cache", "jax")
    assert cache_dirs.executable_cache_dir("x") == \
        os.path.join(REPO, ".cache", "exec", "x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_dirs.jax_compile_cache_dir() == str(tmp_path)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert cache_dirs.enable_jax_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_place_raises_when_the_platform_is_absent():
    import paddle_tpu as paddle
    assert paddle.CPUPlace().jax_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="'tpu'"):
        paddle.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError):
        paddle.CPUPlace(64).jax_device()


# -- one process per chip -----------------------------------------------------
# A parent that has initialised a JAX backend holds the chip; a child that
# needs it then fails or hangs.  Each parent below is run in a fresh
# interpreter with subprocess.Popen/run replaced by a probe that reports
# which backends exist at the moment the first child would start.

_PROBE = r"""
import json, os, subprocess, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {repo!r} + "/tools")

def _probe(*a, **kw):
    import jax._src.xla_bridge as xb
    print("@@" + json.dumps(sorted(xb._backends)), flush=True)
    os._exit(0)          # the first child is the whole question

subprocess.Popen = subprocess.run = _probe
{body}
"""

_PARENTS = {
    "serve_router": (
        "import serve; serve.main(['--router', '--replicas', '2', "
        "'--duration', '0.1'])"),
    "serve_ramp": (
        "import serve; serve.main(['--ramp', '2', '--duration', '0.1'])"),
    "dryrun_multichip": (
        "import __graft_entry__ as g; g.dryrun_multichip(64)"),
}


@pytest.mark.parametrize("parent", sorted(_PARENTS))
def test_parent_has_no_backend_when_it_starts_a_child(parent):
    code = _PROBE.format(repo=REPO, body=_PARENTS[parent])
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("@@")]
    assert lines, f"{parent} started no child: " + p.stderr[-2000:]
    seen = json.loads(lines[-1][2:])
    assert seen == [], \
        f"{parent}: backends {seen} at the point of Popen/run"

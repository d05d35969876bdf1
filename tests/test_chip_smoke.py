"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses to run without a TPU; these tests call its
phase drivers directly, with the same checks, so a broken phase is found
here before a chip call is spent on it. The Pallas sweep runs in Pallas's
interpreter.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_match_reference(smoke, capsys):
    smoke.run_one_chip(seed=3, n_keys=20_000, n_slots=1 << 15,
                       fp_slots=1 << 17, subset_n=512, batches=4, chunk=4096,
                       max_batch=512, use_pallas_sweep=True)
    phases = [line for line in capsys.readouterr().out.splitlines()]
    for name in ("host_load", "host_exact", "microbatch", "served", "sweep",
                 "fp_load", "fp_exact"):
        assert any(f'"phase": "{name}", "ok": true' in p for p in phases), name


def test_mesh_phase_matches_reference(smoke, capsys):
    smoke.run_mesh(seed=4, n_chips=4, n_keys=20_000, per_shard=1 << 13,
                   fp_per_shard=1 << 15, subset_n=512, batches=4, chunk=4096)
    out = capsys.readouterr().out
    assert out.count('_placement", "ok": true') == 2


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_script_fails_without_a_tpu(script):
    """The smoke does not fall back to the CPU."""
    proc = subprocess.run([sys.executable, script], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr

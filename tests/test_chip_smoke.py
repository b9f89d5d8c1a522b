"""``chip_smoke.py`` at tiny sizes on the CPU: its phases run and check
what they claim to check, and the script refuses to run without a TPU."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_a_platform_without_tpu(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied out of the repository, the script has no program to run."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=_cpu_env(),
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compare_fails_on_a_wrong_answer():
    queries = ["a b", '"a b"']
    want = [np.asarray([1, 2]), np.asarray([5])]
    chip_smoke.compare("same", queries, [w.copy() for w in want], want)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare("wrong", queries, [np.asarray([1]), want[1]], want)
    with pytest.raises(chip_smoke.SmokeFailure):  # a rejected query
        chip_smoke.compare("missing", queries, [None, want[1]], want)


def test_main_phase_tiny():
    out = chip_smoke.main_phase(np_docs=300, pos_docs=200, per_kind=4, seed=0)
    assert out["queries"] == 4 * len(chip_smoke.KINDS)
    assert out["new_traces"] == 0
    assert out["device_batches"] >= 2 * len(chip_smoke.KINDS)


def test_kernel_phase_tiny(capsys):
    """Both layouts answer like the host path; interpret mode on the CPU
    leaves no compiled kernel to find, which the phase refuses."""
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="fused, dense kernel step holds no compiled"):
        chip_smoke.kernel_phase(docs=100, per_kind=2, seed=1)
    out = capsys.readouterr().out
    for layout in ("fused", "dense"):
        assert f"kernel probes ({layout}): 12/12 answers equal" in out


def test_mining_phase_tiny():
    out = chip_smoke.mining_phase(docs=300, rlz_docs=200, probes=4, seed=0)
    assert out["clusters"] >= 1 and out["rlz_heads"] >= 1


def test_four_chip_phase_on_four_cpu_devices():
    code = ("import json, chip_smoke; print(json.dumps(chip_smoke."
            "four_chip_phase(np_docs=200, pos_docs=100, per_kind=4, seed=0)))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    per_device = json.loads(out.stdout.strip().splitlines()[-1])[
        "bytes_per_device"]
    assert len(per_device) == 4 and len(set(per_device)) == 1
    assert min(per_device) > 0

"""A rehearsal of a whole run on the CPU, at a tiny size: a configuration
and traffic files of the test's own, the harness's cell function, the
result line's keys, and ``correct`` -- true on the program as it is,
false with the timed path broken underneath."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import Cell, run_cell  # noqa: E402

TINY = {
    "articles": 3, "versions_per_article": 10, "words_per_doc": 30,
    "vocab_size": 60, "zipf": 1.1, "edit_rate": 0.05, "structure": "linear",
    "vocab_seed": 7,
}
CONFIG = {"name": "tiny-archive", "collection": TINY,
          "index": {"kind": "nonpositional", "store": "repair_skip"},
          "frontend": {"max_batch": 4}}
E2E = ["qps", "p50_ms", "device_space_pct", "setup_s"]
UNITS = {"qps": "q/s", "p50_ms": "ms", "device_space_pct": "%", "setup_s": "s"}


def tiny_cell(tmp_path: Path, words: str) -> Cell:
    """The test's own configuration and traffic files, read back."""
    traffic = {"kind": "and", "terms": 2, "words": words, "loop": "closed",
               "clients": 4, "rounds": 6, "warmup": [4]}
    cfile = tmp_path / "tiny-archive.json"
    tfile = tmp_path / f"and-{words}.json"
    cfile.write_text(json.dumps(CONFIG))
    tfile.write_text(json.dumps(traffic))
    return Cell(name=f"tiny.and-{words}", config=json.loads(cfile.read_text()),
                traffic=json.loads(tfile.read_text()),
                end_to_end=[{"name": n, "unit": UNITS[n]} for n in E2E],
                per_layer=[])


def run(tmp_path, words, seed=2**31 + 5):
    return run_cell(tiny_cell(tmp_path, words), seed=seed, seconds=1.0,
                    trace=False, cache=tmp_path / "cache", compile_cache=False)


@pytest.mark.parametrize("words", ["low", "high"])
def test_tiny_cell_runs_and_is_correct(tmp_path, words):
    result = run(tmp_path, words)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == set(E2E)
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    # a second run of the seed does the same work again: nothing is reused
    again = run(tmp_path, words)
    assert again["correct"] is True
    assert again["attempted"] == result["attempted"]
    assert again["metrics"]["device_space_pct"] == result["metrics"]["device_space_pct"]


def test_the_generator_sends_only_closed_loop_and():
    from bench.traffic import make_traffic

    with pytest.raises(ValueError, match="closed-loop AND"):
        make_traffic({"kind": "phrase", "loop": "closed"}, None, 1)
    with pytest.raises(ValueError, match="closed-loop AND"):
        make_traffic({"kind": "and", "loop": "open"}, None, 1)


def _alter_one(results):
    out = [np.asarray(r).copy() for r in results]
    for r in out:
        if len(r):
            r[-1] += 1  # a wrong id where the answer is produced
            break
    else:
        out[0] = np.asarray([0], np.int64)
    return out


def _drop_half(results):
    half = len(results) // 2
    return [np.zeros(0, np.int64) if i >= half else r
            for i, r in enumerate(results)]


@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer-altered", "half-batch-left-out"])
@pytest.mark.parametrize("words", ["low", "high"])
def test_a_broken_timed_path_reads_not_correct(tmp_path, monkeypatch, words, fault):
    from repro.serving.engine import BatchedServer

    honest = BatchedServer._sweep

    def broken(self, *a, **kw):
        return fault(honest(self, *a, **kw))

    monkeypatch.setattr(BatchedServer, "_sweep", broken)
    result = run(tmp_path, words, seed=11)
    assert result["correct"] is False, result["checks"]


def test_run_py_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "wiki-archive.and-low", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert not proc.stdout.strip().endswith("}")

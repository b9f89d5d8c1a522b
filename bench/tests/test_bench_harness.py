"""A rehearsal of a whole run on the CPU, at a tiny size: a configuration,
a traffic file, a per-layer metric and ``BENCHMARK.json`` entries of the
test's own, staged beside the committed entries in a root of their own
and read back by ``load_cell``, the harness's cell
function, the result line's keys, and ``correct`` -- true on the program
as it is, false with the timed path broken underneath.  Each case is one
index kind under one traffic kind: a non-positional index under AND
traffic, a positional one under phrase traffic."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import Run, load_cell, read_metric, run_cell  # noqa: E402

TINY = {
    "articles": 3, "versions_per_article": 10, "words_per_doc": 30,
    "vocab_size": 60, "zipf": 1.1, "edit_rate": 0.05, "structure": "linear",
    "vocab_seed": 7,
}
E2E = ["qps", "p50_ms", "device_space_pct", "setup_s"]
LOOP = {"loop": "closed", "clients": 4, "rounds": 6, "warmup": [4]}
#: case -> (index kind, traffic mix name, traffic file)
CASES = {
    "low": ("nonpositional", "and-low",
            dict(kind="and", terms=2, words="low", **LOOP)),
    "high": ("nonpositional", "and-high",
             dict(kind="and", terms=2, words="high", **LOOP)),
    "phrase": ("positional", "phrase",
               dict(kind="phrase", terms=2, positions="uniform", **LOOP)),
}


def stage(root: Path, index_kind: str, mix: str, traffic: dict) -> str:
    """Writes one cell's files and ``BENCHMARK.json`` entries under
    ``root``, as a PR adding a cell would: a configuration, a traffic
    file, a per-layer metric of the cell's own with its reader, and their
    entries beside the committed metric entries, none of which is edited.
    Returns the cell's name."""
    config = {"name": f"tiny-{index_kind}", "collection": TINY,
              "index": {"kind": index_kind, "store": "repair_skip"},
              "frontend": {"max_batch": 4}}
    cell = f"{config['name']}.{mix}"
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics",
                    dirs_exist_ok=True)  # the committed readers
    for d in ("configs", "traffic"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(traffic))
    own = f"device.idle_share.{mix}"
    shutil.copy(ROOT / "bench" / "metrics" / "device.idle_share.sweep.py",
                root / "bench" / "metrics" / f"{own}.py")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config["name"],
                            "file": f"bench/configs/{config['name']}.json"})
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": mix, "chips": 1})
    spec["per_layer"].append({"name": own, "unit": "%", "moves": "qps",
                              "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def run(tmp_path, case, seed=2**31 + 5):
    cell = load_cell(stage(tmp_path, *CASES[case]), root=tmp_path)
    return run_cell(cell, seed=seed, seconds=1.0, trace=False,
                    cache=tmp_path / "cache", compile_cache=False)


def test_load_cell_reads_every_file_from_its_root(tmp_path):
    index_kind, mix, traffic = CASES["phrase"]
    cell = load_cell(stage(tmp_path, index_kind, mix, traffic), root=tmp_path)
    assert cell.name == "tiny-positional.phrase"
    assert cell.traffic == traffic
    assert cell.config["index"] == {"kind": "positional", "store": "repair_skip"}
    # the committed end-to-end metrics, and of the per-layer ones its own
    assert [m["name"] for m in cell.end_to_end] == E2E
    assert [m["name"] for m in cell.per_layer] == ["device.idle_share.phrase"]
    assert cell.chips == 1 and cell.root == tmp_path
    run = Run(trace=SimpleNamespace(busy_s=3.0, window_s=4.0))
    assert read_metric("device.idle_share.phrase", run, cell.root) == 25.0


@pytest.mark.parametrize("case", CASES)
def test_tiny_cell_runs_and_is_correct(tmp_path, case):
    result = run(tmp_path, case)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == set(E2E)
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    # a second run of the seed does the same work again: nothing is reused
    again = run(tmp_path, case)
    assert again["correct"] is True
    assert again["attempted"] == result["attempted"]
    assert again["metrics"]["device_space_pct"] == result["metrics"]["device_space_pct"]


def test_the_generator_sends_only_closed_loop_and():
    """Closed-loop AND and phrase traffic are sent; open loop and unknown
    kinds are refused."""
    from bench.collection import generate_docs
    from bench.reference import PositionalReference
    from bench.traffic import make_traffic

    docs = generate_docs(TINY, 1)
    phrase = make_traffic(CASES["phrase"][2], PositionalReference(docs), 1)
    assert all(q[0] == q[-1] == '"' for s in phrase.streams for q in s)
    with pytest.raises(ValueError, match="closed-loop traffic"):
        make_traffic({"kind": "and", "loop": "open"}, None, 1)
    with pytest.raises(ValueError, match="closed-loop traffic"):
        make_traffic({"kind": "phrase", "loop": "open"}, None, 1)
    with pytest.raises(ValueError, match=r"\['and', 'phrase'\]"):
        make_traffic({"kind": "or", "loop": "closed"}, None, 1)


def test_an_unknown_index_kind_fails_at_set_up(tmp_path):
    _, mix, traffic = CASES["low"]
    cell = load_cell(stage(tmp_path, "trigram", mix, traffic), root=tmp_path)
    with pytest.raises(ValueError, match="'nonpositional', 'positional'"):
        run_cell(cell, seed=1, seconds=1.0, trace=False,
                 cache=tmp_path / "cache", compile_cache=False)


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
@pytest.mark.parametrize("case", CASES)
def test_a_broken_timed_path_reads_not_correct(tmp_path, monkeypatch, case, fault):
    from repro.serving.engine import BatchedServer

    honest = BatchedServer._sweep

    def broken(self, *a, **kw):
        return fault(honest(self, *a, **kw))

    monkeypatch.setattr(BatchedServer, "_sweep", broken)
    result = run(tmp_path, case, seed=11)
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

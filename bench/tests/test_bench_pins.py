"""Pins of what the ``wiki-archive.and-low`` cell reads, taken from the
harness before it learned positional indexes and phrase traffic: the
query streams and warm-up of ``bench/traffic/and-low.json``, and the
bytes behind ``device_space_pct`` and ``serve_step_roofline`` on a tiny
non-positional index.  A change that moves any of them changes the cell."""

import hashlib
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, roofline  # noqa: E402
from bench.collection import generate_docs  # noqa: E402
from bench.reference import WordReference, parse  # noqa: E402
from bench.traffic import make_traffic  # noqa: E402

SEEDS = [1, 2**31 + 5]


@pytest.mark.parametrize("seed,digest", zip(SEEDS, [
    "7538a6c26408e91b44316cfec0f1869ce3d51397456f994adf0b374cddae5de9",
    "b105712b95297f4dc6c79445b514eb10651eb2df3d540e868859fd2c6fc2d182"]))
def test_and_low_query_streams(seed, digest):
    """The cell's traffic file over its configuration cut to 2 articles of
    3 versions, which hold its 4,112 distinct queries."""
    config = json.loads((ROOT / "bench/configs/wiki-archive.json").read_text())
    spec = json.loads((ROOT / "bench/traffic/and-low.json").read_text())
    docs = generate_docs(dict(config["collection"], articles=2,
                              versions_per_article=3), seed)
    t = make_traffic(spec, WordReference(docs), seed)
    blob = json.dumps({"warmup": t.warmup, "streams": t.streams}).encode()
    assert hashlib.sha256(blob).hexdigest() == digest


@pytest.mark.parametrize("seed,server,sweep", zip(SEEDS, [11918, 13176],
                                                 [2312, 2416]))
def test_and_low_device_and_sweep_bytes(tmp_path, seed, server, sweep):
    config = {"name": "tiny-archive",
              "collection": {"articles": 3, "versions_per_article": 10,
                             "words_per_doc": 30, "vocab_size": 60,
                             "zipf": 1.1, "edit_rate": 0.05,
                             "structure": "linear", "vocab_seed": 7},
              "index": {"kind": "nonpositional", "store": "repair_skip"},
              "frontend": {"max_batch": 4}}
    traffic = {"kind": "and", "terms": 2, "words": "low", "loop": "closed",
               "clients": 4, "rounds": 6, "warmup": [4]}
    _, wref, session = harness.build_index(config, seed, tmp_path)
    queries = [parse(q) for s in make_traffic(traffic, wref, seed).streams
               for q in s]
    lens = [len(wref.answer(q)) for q in queries]
    assert harness.server_bytes(session) == server
    assert roofline.sweep_bytes(session.index, queries, lens) == sweep

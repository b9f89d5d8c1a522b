"""The plain reference agrees with the program's host path at a tiny size
on the CPU, so a disagreement on the chip is the device path's; and the
control, the reference with exactness broken, reads as not correct."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import reference  # noqa: E402
from bench.collection import generate_docs  # noqa: E402
from bench.harness import check  # noqa: E402
from bench.reference import WordReference  # noqa: E402
from bench.traffic import make_traffic  # noqa: E402

SPEC = {"articles": 4, "versions_per_article": 12, "words_per_doc": 40,
        "vocab_size": 80, "zipf": 1.1, "edit_rate": 0.05, "structure": "linear",
        "vocab_seed": 3}


@pytest.fixture(scope="module")
def world():
    from repro.core.index import NonPositionalIndex
    from repro.serving.session import Session

    docs = generate_docs(SPEC, seed=2**33 + 1)
    docs[5] = "The cat, and THE dog: a cat!"  # separators, case, stopwords
    host = Session.build(NonPositionalIndex.build(docs, store="repair_skip"),
                         device=False)
    return docs, WordReference(docs), host


def _queries(wref, words, n=40, terms=2):
    spec = {"kind": "and", "terms": terms, "words": words, "loop": "closed",
            "clients": 1, "rounds": n, "warmup": []}
    return make_traffic(spec, wref, seed=9).streams[0]


@pytest.mark.parametrize("words,terms", [("low", 2), ("high", 2), ("low", 3)])
def test_and_equals_the_host_path(world, words, terms):
    _, wref, host = world
    qs = _queries(wref, words, terms=terms)
    for q, got in zip(qs, host.execute(qs)):
        np.testing.assert_array_equal(wref.conjunctive(q.split()), got, err_msg=q)


def test_separators_case_and_stopwords_follow_the_host_path(world):
    _, wref, host = world
    qs = ["cat dog", "the cat", "Cat DOG", "cat zzz"]
    for q, got in zip(qs, host.execute(qs)):
        np.testing.assert_array_equal(wref.conjunctive(q.split()), got, err_msg=q)
    assert len(wref.conjunctive(["Cat", "DOG"])) == 1
    assert len(wref.conjunctive(["the", "cat"])) == 0  # a stopword: no answer


def test_word_sets_split_at_the_median_df(world):
    from bench.traffic import word_set

    _, wref, _ = world
    df = {w: wref.df(w) for w in wref.vocabulary()}
    low, high = word_set(df, "low"), word_set(df, "high")
    assert set(low) | set(high) == {w for w, f in df.items() if f > 0}
    assert max(df[w] for w in low) < min(df[w] for w in high)


@pytest.mark.parametrize("words", ["low", "high"])
def test_the_truncating_control_fails(world, monkeypatch, words):
    _, wref, _ = world
    monkeypatch.setattr(reference, "CONTROL_CAP", 3)
    qs = _queries(wref, words)
    sound = check([(q, wref.conjunctive(q.split())) for q in qs], wref)
    control = check([(q, None) for q in qs], wref, control=True)
    assert sound["wrong_answers"]["value"] == 0
    assert control["wrong_answers"]["value"] > control["wrong_answers"]["limit"]

"""The plain references agree with the program's host path at a tiny size
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
from bench.reference import PositionalReference, WordReference, parse  # noqa: E402
from bench.traffic import make_traffic  # noqa: E402

SPEC = {"articles": 4, "versions_per_article": 12, "words_per_doc": 40,
        "vocab_size": 80, "zipf": 1.1, "edit_rate": 0.05, "structure": "linear",
        "vocab_seed": 3}
#: separators, case, stopwords and runs of blanks
HAND = ["The cat, and THE dog: a cat!",
        "the  cat   sat, on the cat  mat. The cat sat",
        "", "cat"]


def _docs():
    docs = generate_docs(SPEC, seed=2**33 + 1)
    docs[5:5 + len(HAND)] = HAND
    return docs


@pytest.fixture(scope="module")
def world():
    from repro.core.index import NonPositionalIndex
    from repro.serving.session import Session

    docs = _docs()
    host = Session.build(NonPositionalIndex.build(docs, store="repair_skip"),
                         device=False)
    return docs, WordReference(docs), host


@pytest.fixture(scope="module")
def positional():
    from repro.core.index import PositionalIndex
    from repro.serving.session import Session

    docs = _docs()
    pidx = PositionalIndex.build(docs, store="repair_skip")
    return PositionalReference(docs), pidx, Session.build(None, positional=pidx,
                                                           device=False)


def _queries(wref, words, n=40, terms=2):
    spec = {"kind": "and", "terms": terms, "words": words, "loop": "closed",
            "clients": 1, "rounds": n, "warmup": []}
    return make_traffic(spec, wref, seed=9).streams[0]


def _phrases(pref, n=40, terms=2, seed=9):
    spec = {"kind": "phrase", "terms": terms, "positions": "uniform",
            "loop": "closed", "clients": 1, "rounds": n, "warmup": []}
    return make_traffic(spec, pref, seed=seed).streams[0]


def test_parse_tells_phrases_from_and():
    """A phrase's quotes are stripped; an AND query's words are kept."""
    assert parse("cat dog") == ["cat", "dog"]
    assert parse('"cat dog"') == ["cat", "dog"]


@pytest.mark.parametrize("words,terms", [("low", 2), ("high", 2), ("low", 3)])
def test_and_equals_the_host_path(world, words, terms):
    _, wref, host = world
    qs = _queries(wref, words, terms=terms)
    for q, got in zip(qs, host.execute(qs)):
        np.testing.assert_array_equal(wref.answer(q.split()), got, err_msg=q)


def test_separators_case_and_stopwords_follow_the_host_path(world):
    _, wref, host = world
    qs = ["cat dog", "the cat", "Cat DOG", "cat zzz"]
    for q, got in zip(qs, host.execute(qs)):
        np.testing.assert_array_equal(wref.answer(q.split()), got, err_msg=q)
    assert len(wref.answer(["Cat", "DOG"])) == 1
    assert len(wref.answer(["the", "cat"])) == 0  # a stopword: no answer


@pytest.mark.parametrize("terms", [2, 3])
def test_phrase_equals_the_positional_index(positional, terms):
    pref, pidx, host = positional
    qs = _phrases(pref, terms=terms)
    for q, got in zip(qs, host.execute(qs)):
        want = pref.answer(parse(q))
        assert len(want) > 0, q  # drawn from the text: it occurs
        np.testing.assert_array_equal(want, got, err_msg=q)
        np.testing.assert_array_equal(want, pidx.query_phrase(parse(q)))


@pytest.mark.parametrize("phrase", [
    "cat sat", "the cat", "The cat", "cat", "a cat", "THE dog", "cat mat",
    "cat, and", "dog: a", "sat on", "zzz cat", "cat the"])
def test_hand_written_phrases_follow_the_positional_index(positional, phrase):
    """Punctuation, case, runs of blanks and the separator after each
    document, against ``PositionalIndex.query_phrase``."""
    pref, pidx, _ = positional
    np.testing.assert_array_equal(pref.answer(phrase.split()),
                                  pidx.query_phrase(phrase.split()))
    assert len(pref.tokens) == pidx.n_tokens


def test_phrase_traffic_is_distinct_by_exact_words_and_repeats_by_seed(positional):
    pref, _, _ = positional
    qs = _phrases(pref, n=120)
    assert len(set(qs)) == len(qs) and qs == _phrases(pref, n=120)
    assert qs != _phrases(pref, n=120, seed=10)


def test_word_sets_split_at_the_median_df(world):
    from bench.traffic import word_set

    _, wref, _ = world
    df = {w: wref.df(w) for w in wref.vocabulary()}
    low, high = word_set(df, "low"), word_set(df, "high")
    assert set(low) | set(high) == {w for w, f in df.items() if f > 0}
    assert max(df[w] for w in low) < min(df[w] for w in high)


@pytest.mark.parametrize("words", ["low", "high", "phrase"])
def test_the_truncating_control_fails(world, positional, monkeypatch, words):
    if words == "phrase":
        ref = positional[0]
        qs = _phrases(ref)
    else:
        ref = world[1]
        qs = _queries(ref, words)
    monkeypatch.setattr(reference, "CONTROL_CAP", 3)
    sound = check([(q, ref.answer(parse(q))) for q in qs], ref)
    control = check([(q, None) for q in qs], ref, control=True)
    assert sound["wrong_answers"]["value"] == 0
    assert control["wrong_answers"]["value"] > control["wrong_answers"]["limit"]

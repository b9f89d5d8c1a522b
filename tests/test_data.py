"""Collections and tokenization."""

from repro.data import generate_collection
from repro.data.text import detokenize, tokenize


def test_tokenize_roundtrip():
    doc = "Hello world, this is a test!  Multi  space."
    assert detokenize(tokenize(doc)) == doc


def test_collection_determinism():
    a = generate_collection(n_articles=2, versions_per_article=3, words_per_doc=20, seed=5)
    b = generate_collection(n_articles=2, versions_per_article=3, words_per_doc=20, seed=5)
    assert a.docs == b.docs


def test_collection_structures_differ():
    lin = generate_collection(structure="linear", seed=1, n_articles=2,
                              versions_per_article=4, words_per_doc=30)
    cha = generate_collection(structure="chaotic", seed=1, n_articles=2,
                              versions_per_article=4, words_per_doc=30)
    assert lin.docs != cha.docs

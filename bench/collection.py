"""The benchmark's own copy of the versioned-collection generator.

Frozen here so that no change to the program can move the data a cell
measures.  It is the generator of the program's ``repro.data.collection``
(``_make_word``, ``_mutate``, ``generate_collection``, linear versioning)
with one change: the vocabulary comes from the configuration's own
``vocab_seed`` and only the documents come from the run's seed.  The
vocabulary's word lengths set the text bytes per word, and with a
vocabulary drawn per run seed the collection's size moved by up to 8%
from seed to seed; a deployment keeps its language and changes its
documents.
"""

from __future__ import annotations

import numpy as np

_CONSONANTS = "bcdfghjklmnpqrstvwz"
_VOWELS = "aeiou"


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use (``stream``) of a run seed.
    Any whole number is a seed, also one past 32 or 64 bits."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed) % 2**64, spawn_key=(stream,)))


def _make_word(rng: np.random.Generator) -> str:
    n_syll = int(rng.integers(1, 4))
    return "".join(
        _CONSONANTS[int(rng.integers(len(_CONSONANTS)))]
        + _VOWELS[int(rng.integers(len(_VOWELS)))]
        for _ in range(n_syll)
    ) + (_CONSONANTS[int(rng.integers(len(_CONSONANTS)))]
         if rng.random() < 0.4 else "")


def _mutate(words: list[str], rng: np.random.Generator, rate: float,
            vocab: list[str]) -> list[str]:
    out: list[str] = []
    i = 0
    n = len(words)
    while i < n:
        r = rng.random()
        if r < rate / 3:  # delete
            i += 1
        elif r < 2 * rate / 3:  # substitute
            out.append(vocab[int(rng.integers(len(vocab)))])
            i += 1
        elif r < rate:  # insert
            out.append(vocab[int(rng.integers(len(vocab)))])
        else:
            out.append(words[i])
            i += 1
    if not out:
        out = [vocab[0]]
    return out


def make_vocab(vocab_size: int, vocab_seed: int) -> list[str]:
    rng = np.random.default_rng(vocab_seed)
    vocab: list[str] = []
    seen: set[str] = set()
    while len(vocab) < vocab_size:
        w = _make_word(rng)
        if w not in seen:
            seen.add(w)
            vocab.append(w)
    return vocab


def generate_docs(spec: dict, seed: int) -> list[str]:
    """The documents of a configuration's ``collection`` block for one run
    seed: ``articles`` articles of ``versions_per_article`` linear versions,
    each version an edit of the one before at ``edit_rate``; base articles
    draw ``words_per_doc`` words from a Zipf(``zipf``) law over the
    vocabulary."""
    vocab = make_vocab(int(spec["vocab_size"]), int(spec["vocab_seed"]))
    rng = seed_rng(seed, 0)
    probs = 1.0 / np.arange(1, len(vocab) + 1) ** float(spec["zipf"])
    probs /= probs.sum()
    rate = float(spec["edit_rate"])
    docs: list[str] = []
    for _ in range(int(spec["articles"])):
        words = [vocab[int(i)] for i in rng.choice(
            len(vocab), size=int(spec["words_per_doc"]), p=probs)]
        docs.append(" ".join(words))
        for _ in range(1, int(spec["versions_per_article"])):
            words = _mutate(words, rng, rate, vocab)
            docs.append(" ".join(words))
    return docs

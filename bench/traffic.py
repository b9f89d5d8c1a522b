"""The one traffic generator: a traffic file's parameters, the reference
over a configuration's documents and a run seed in; the queries of a run
out.

A traffic file (``bench/traffic/<mix>.json``) holds:

* ``kind``: the query kind, one of :data:`KINDS`:

  - ``and`` (``w1 w2 ...``), over a ``WordReference``: ``terms`` distinct
    words per query, from ``words``: ``low`` -- the analyzed words whose
    document frequency is at most the median, or ``high`` -- those above
    it (the split of the paper's query protocol);
  - ``phrase`` (``"w1 w2 ..."``, in quotes), over a
    ``PositionalReference``: ``terms`` consecutive word tokens of the
    text, at ``positions``: ``uniform`` -- a document drawn uniformly
    among those with ``terms`` consecutive words, then a start drawn
    uniformly among that document's such runs (the paper's phrases drawn
    from the text, section 5), so every phrase occurs at least once;

* ``loop``: ``closed``, with ``clients`` clients, each sending its next
  query when its answer arrives, from a stream of ``rounds`` queries;
* ``warmup``: the batch sizes the warm-up sends through the served path,
  one batch of each, before the window opens.

Every query of a run, warm-up included, differs from every other: a
repeat would be answered from the frontend's result cache, and in a
closed loop would pull its client out of step with the others.  AND
queries with the same words in another order count as one; phrases, whose
order matters, count by their exact words.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .collection import seed_rng


@dataclass
class Traffic:
    warmup: list[list[str]]  # batches, each sent all at once
    streams: list[list[str]]  # one per client


def word_set(df: dict[str, int], which: str) -> list[str]:
    """The query words of a ``words`` setting, in first-seen order."""
    live = {w: f for w, f in df.items() if f > 0}
    med = float(np.median(list(live.values())))
    if which == "low":
        return [w for w, f in live.items() if f <= med]
    if which == "high":
        return [w for w, f in live.items() if f > med]
    raise ValueError(f"unknown word set {which!r}")


class _Distinct:
    """Draws queries from ``draw`` until one is new."""

    def __init__(self, draw, key=lambda q: q):
        self.draw, self.key, self.seen = draw, key, set()

    def __call__(self) -> str:
        for _ in range(10_000):
            q = self.draw()
            k = self.key(q)
            if k not in self.seen:
                self.seen.add(k)
                return q
        raise RuntimeError("the traffic ran out of distinct queries")


def _and_queries(spec: dict, wref, rng: np.random.Generator) -> _Distinct:
    n_terms = int(spec["terms"])
    words = word_set({w: wref.df(w) for w in wref.vocabulary()},
                     spec["words"])

    def draw() -> str:
        picked = rng.choice(len(words), size=n_terms, replace=False)
        return " ".join(words[int(i)] for i in picked)
    # "a b" and "b a" have one answer: count them as one query
    return _Distinct(draw, key=lambda q: frozenset(q.split()))


def _phrase_queries(spec: dict, pref, rng: np.random.Generator) -> _Distinct:
    k = int(spec["terms"])
    if spec["positions"] != "uniform":
        raise ValueError(f"unknown phrase positions {spec['positions']!r}")
    word = pref.is_word[pref.tokens]
    m = len(word) - k + 1
    fits = np.ones(max(m, 0), bool)  # k word tokens from here on
    for i in range(k):
        fits &= word[i:i + m]
    starts = np.flatnonzero(fits)
    # a run of words never crosses the separator after each document
    bounds = np.searchsorted(starts, np.append(pref.doc_starts, len(word)))
    counts = np.diff(bounds)
    docs = np.flatnonzero(counts)

    def draw() -> str:
        d = docs[int(rng.integers(len(docs)))]
        s = starts[bounds[d] + int(rng.integers(counts[d]))]
        return '"' + " ".join(pref.text[t] for t in pref.tokens[s:s + k]) + '"'
    return _Distinct(draw)


#: traffic kind -> its query source, from (spec, reference, rng)
KINDS = {"and": _and_queries, "phrase": _phrase_queries}


def make_traffic(spec: dict, ref, seed: int) -> Traffic:
    """The queries of one run; ``ref`` is the reference over the run's
    documents: a ``WordReference`` for ``and`` traffic, whose document
    frequencies split the words, a ``PositionalReference`` for
    ``phrase`` traffic, whose token stream the phrases are drawn from."""
    if spec["kind"] not in KINDS or spec["loop"] != "closed":
        raise ValueError(f"the generator sends closed-loop traffic of the "
                         f"kinds {sorted(KINDS)}, not {spec['loop']} "
                         f"{spec['kind']}")
    next_query = KINDS[spec["kind"]](spec, ref, seed_rng(seed, 1))
    warmup = [[next_query() for _ in range(int(b))] for b in spec["warmup"]]
    clients, rounds = int(spec["clients"]), int(spec["rounds"])
    pool = [next_query() for _ in range(clients * rounds)]
    return Traffic(warmup=warmup, streams=[pool[c::clients] for c in range(clients)])

"""The one traffic generator: a traffic file's parameters, the reference
over a configuration's documents and a run seed in; the queries of a run
out.

A traffic file (``bench/traffic/<mix>.json``) holds:

* ``kind``: ``and`` (``w1 w2 ...``), the one kind the cells send;
* ``terms``: words per query;
* ``words``: ``low`` -- the analyzed words whose document frequency is at
  most the median, or ``high`` -- those above it (the split of the paper's
  query protocol);
* ``loop``: ``closed``, with ``clients`` clients, each sending its next
  query when its answer arrives, from a stream of ``rounds`` queries;
* ``warmup``: the batch sizes the warm-up sends through the served path,
  one batch of each, before the window opens.

Words of a query are distinct, and every query of a run, warm-up included,
differs from every other: a repeat would be answered from the frontend's
result cache, and in a closed loop would pull its client out of step with
the others.
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


def make_traffic(spec: dict, wref, seed: int) -> Traffic:
    """The queries of one run; ``wref`` is the reference over the run's
    documents, whose document frequencies split the words."""
    if spec["kind"] != "and" or spec["loop"] != "closed":
        raise ValueError(f"the generator sends closed-loop AND traffic, not "
                         f"{spec['loop']} {spec['kind']}")
    n_terms = int(spec["terms"])
    rng = seed_rng(seed, 1)
    words = word_set({w: wref.df(w) for w in wref.vocabulary()},
                     spec["words"])

    def draw() -> str:
        picked = rng.choice(len(words), size=n_terms, replace=False)
        return " ".join(words[int(i)] for i in picked)
    # "a b" and "b a" have one answer: count them as one query
    next_query = _Distinct(draw, key=lambda q: frozenset(q.split()))
    warmup = [[next_query() for _ in range(int(b))] for b in spec["warmup"]]
    clients, rounds = int(spec["clients"]), int(spec["rounds"])
    pool = [next_query() for _ in range(clients * rounds)]
    return Traffic(warmup=warmup, streams=[pool[c::clients] for c in range(clients)])

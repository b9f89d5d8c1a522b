"""Shared fixtures. NOTE: no XLA device-count flags here — smoke tests and
benches must see 1 device (test_distributed sets its own 8-device env in a
subprocess)."""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_repetitive_lists(rng, n_lists=30, n_docs=2000, block=20, p=0.3, noise=0.02):
    """Posting lists with versioned-collection structure."""
    lists = []
    for _ in range(n_lists):
        base = rng.random(n_docs // block) < p
        present = np.repeat(base, block) ^ (rng.random(n_docs) < noise)
        l = np.flatnonzero(present).astype(np.int64)
        if len(l) == 0:
            l = np.asarray([int(rng.integers(0, n_docs))], dtype=np.int64)
        lists.append(l)
    return lists


@pytest.fixture(scope="session")
def rep_lists():
    return make_repetitive_lists(np.random.default_rng(42))


@pytest.fixture(scope="session")
def small_collection():
    from repro.data import generate_collection

    return generate_collection(n_articles=6, versions_per_article=8,
                               words_per_doc=100, seed=3)

"""The plain reference: the answers of the AND queries the cells send,
computed by brute force from the raw generated documents.

It imports nothing of the program and reads nothing the program made.
It follows the semantics the program documents:

* tokens are maximal runs of ``[A-Za-z0-9]`` or of other characters, with
  single blanks dropped (the spaceless-words model);
* document-level (non-positional) terms are case-folded words, less the
  20 stopwords of the paper's setup (section 5.1.3);
* an AND answer is the sorted ids of the documents that hold every query
  term; a query with an unknown term or a stopword has none.

The ``cap`` of :meth:`WordReference.conjunctive` is the control: the same
reference with its one guarantee, exactness, broken as a later change might
be tempted to break it; ``bench/control.py`` reads it at a cell's size and
it sets the upper end of the limit.
"""

from __future__ import annotations

import re

import numpy as np

_TOKEN_RE = re.compile(r"[A-Za-z0-9]+|[^A-Za-z0-9]+")
_WORD_RE = re.compile(r"[A-Za-z0-9]")
_PLAIN = re.compile(r"[A-Za-z0-9]+( [A-Za-z0-9]+)*")
STOPWORDS = frozenset({
    "the", "of", "and", "a", "to", "in", "is", "you", "that", "it",
    "he", "was", "for", "on", "are", "as", "with", "his", "they", "i",
})
#: postings the truncating control keeps of a query's driving list: about
#: what one candidate window of the device sweep decodes
CONTROL_CAP = 2048


def tokens(doc: str) -> list[str]:
    return [t for t in _TOKEN_RE.findall(doc) if t != " "]


def word_term(tok: str) -> str | None:
    if not _WORD_RE.match(tok):
        return None
    w = tok.lower()
    return None if w in STOPWORDS else w


def parse(query: str) -> list[str]:
    """The words of an AND query string."""
    return query.split()


class WordReference:
    """Document-level answers: AND over analyzed words."""

    def __init__(self, docs: list[str]):
        term_id: dict[str, int] = {}
        doc_of: list[int] = []
        term_of: list[int] = []
        for d, doc in enumerate(docs):
            if _PLAIN.fullmatch(doc):  # words and single blanks only
                terms = [w for w in doc.lower().split() if w not in STOPWORDS]
            else:
                terms = [w for w in map(word_term, tokens(doc)) if w is not None]
            term_of.extend(term_id.setdefault(w, len(term_id)) for w in terms)
            doc_of.extend([d] * len(terms))
        self.term_id = term_id
        self.n_docs = len(docs)
        n_terms = len(term_id)
        key = np.asarray(term_of, np.int64) * self.n_docs + np.asarray(doc_of, np.int64)
        pairs = np.unique(key)  # sorted by (term, doc)
        self._docs = pairs % self.n_docs
        self._off = np.searchsorted(pairs // self.n_docs, np.arange(n_terms + 1))

    def df(self, term: str) -> int:
        t = self.term_id.get(term)
        return 0 if t is None else int(self._off[t + 1] - self._off[t])

    def postings(self, term: str) -> np.ndarray:
        t = self.term_id.get(term)
        if t is None:
            return np.zeros(0, np.int64)
        return self._docs[self._off[t]:self._off[t + 1]]

    def vocabulary(self) -> list[str]:
        """Analyzed words in first-seen order."""
        return list(self.term_id)

    def conjunctive(self, words: list[str], cap: int | None = None) -> np.ndarray:
        terms = [word_term(w) for w in words]
        if any(t is None or t not in self.term_id for t in terms):
            return np.zeros(0, np.int64)
        lists = sorted((self.postings(t) for t in terms), key=len)
        out = lists[0][:cap] if cap is not None else lists[0]
        for other in lists[1:]:
            out = np.intersect1d(out, other, assume_unique=True)
        return out.astype(np.int64)

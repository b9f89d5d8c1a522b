"""The plain references: the answers of the queries the cells send,
computed by brute force from the raw generated documents.

They import nothing of the program and read nothing the program made.
They follow the semantics the program documents:

* tokens are maximal runs of ``[A-Za-z0-9]`` or of other characters, with
  single blanks dropped (the spaceless-words model);
* document-level (non-positional) terms are case-folded words, less the
  20 stopwords of the paper's setup (section 5.1.3);
* an AND answer is the sorted ids of the documents that hold every query
  term; a query with an unknown term or a stopword has none;
* a phrase answer is the sorted global positions of the first token of
  each occurrence (:class:`PositionalReference` states the token model).

A query string is an AND query (``w1 w2 ...``) or, in double quotes, a
phrase (``"w1 w2 ..."``); each reference answers the one kind its index
serves, with ``answer(terms, cap=None)``.

The ``cap`` of :meth:`WordReference.answer` and of
:meth:`PositionalReference.answer` is the control: the same reference with
its one guarantee, exactness, broken as a later change might be tempted to
break it; ``bench/control.py`` reads it at a cell's size and it sets the
upper end of the limit.
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
    """The words of a query string, a phrase's quotes stripped."""
    if len(query) >= 2 and query[0] == query[-1] == '"':
        query = query[1:-1]
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

    def answer(self, words: list[str], cap: int | None = None) -> np.ndarray:
        """The AND answer: ids of the documents that hold every word."""
        terms = [word_term(w) for w in words]
        if any(t is None or t not in self.term_id for t in terms):
            return np.zeros(0, np.int64)
        lists = sorted((self.postings(t) for t in terms), key=len)
        out = lists[0][:cap] if cap is not None else lists[0]
        for other in lists[1:]:
            out = np.intersect1d(out, other, assume_unique=True)
        return out.astype(np.int64)


class PositionalReference:
    """Phrase answers by shifted equality over the whole token stream.

    The program's positional token model (``repro.data.text.tokenize`` and
    ``PositionalIndex.build``), restated:

    * a token is a maximal run of ``[A-Za-z0-9]`` (a word) or of any other
      characters (a separator), in document order;
    * a separator that is exactly one blank is dropped; any other
      separator, two blanks or ``", "`` among them, is a token of its own;
    * case is kept and no word is dropped: ``Cat`` and ``cat`` are two
      tokens, and stopwords are tokens;
    * after each document comes one separator token of its own, which no
      query matches, so a phrase never spans two documents;
    * a position is a token's offset in the stream of all documents, in
      document order, separators included.
    """

    def __init__(self, docs: list[str]):
        term_id: dict[str, int] = {}  # ids from 1; 0 is the document separator
        ids: list[int] = []
        starts = np.zeros(len(docs), np.int64)
        for d, doc in enumerate(docs):
            starts[d] = len(ids)
            toks = doc.split(" ") if _PLAIN.fullmatch(doc) else tokens(doc)
            ids.extend([term_id.setdefault(t, len(term_id) + 1) for t in toks])
            ids.append(0)
        self.term_id = term_id
        self.tokens = np.asarray(ids, np.int32)
        self.doc_starts = starts
        #: token id -> its text (id 0, the document separator, has none)
        self.text = [""] + list(term_id)
        #: token id -> whether it is a word
        self.is_word = np.asarray([False] + [bool(_WORD_RE.match(t)) for t in term_id])
        self._count = np.bincount(self.tokens, minlength=len(self.text))

    def df(self, token: str) -> int:
        """Occurrences of a token."""
        t = self.term_id.get(token)
        return 0 if t is None else int(self._count[t])

    def answer(self, words: list[str], cap: int | None = None) -> np.ndarray:
        """The phrase answer: global positions of each occurrence's first
        token."""
        ids = [self.term_id.get(w) for w in words]
        n, k = len(self.tokens), len(ids)
        if None in ids or k > n:
            return np.zeros(0, np.int64)
        m = n - k + 1
        match = np.ones(m, bool)
        for i, t in enumerate(ids):
            match &= self.tokens[i:i + m] == t
        if cap is not None:
            # keep the occurrences whose rarest word is among that word's
            # first ``cap`` positions
            r = min(range(k), key=lambda i: self._count[ids[i]])
            first = np.flatnonzero(self.tokens == ids[r])[:cap] - r
            kept = np.zeros(m, bool)
            kept[first[(first >= 0) & (first < m)]] = True
            match &= kept
        return np.flatnonzero(match).astype(np.int64)

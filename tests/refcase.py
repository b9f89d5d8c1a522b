"""A randomized versioned collection and its brute-force NumPy answers:
the reference the differential and device-path tests compare against."""

import numpy as np

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.text import STOPWORDS, is_word_token, tokenize
from repro.serving.plan import parse_query


class RefCase:
    """One randomized collection plus its brute-force answers."""

    def __init__(self, rate: float, seed: int):
        self.rate = rate
        self.seed = seed
        self.col = generate_collection(n_articles=2, versions_per_article=4,
                                       words_per_doc=45, edit_rate=rate,
                                       seed=seed)
        self.docs = self.col.docs
        # folded word-token sets / counts per doc (non-positional semantics)
        self.word_sets = []
        self.tok_lists = []
        self.term_lists = []  # default-analyzer terms per doc (BM25 semantics)
        for doc in self.docs:
            toks = tokenize(doc)
            self.tok_lists.append(toks)
            terms = [t.lower() for t in toks if is_word_token(t)
                     and t.lower() not in STOPWORDS]
            self.term_lists.append(terms)
            self.word_sets.append(set(terms))
        # reference vocab (identical across backends): build once with vbyte
        self.ref_np = NonPositionalIndex.build(self.docs, store="vbyte")
        self.ref_pos = PositionalIndex.build(self.docs, store="vbyte",
                                             keep_text=True)
        self.stream = self.ref_pos.token_stream

    # -- brute-force answers -------------------------------------------
    def brute_docs(self, words) -> np.ndarray:
        if any(self.ref_np.word_id(w) is None for w in words):
            return np.zeros(0, dtype=np.int64)
        return np.asarray([d for d, s in enumerate(self.word_sets)
                           if all(w in s for w in words)], dtype=np.int64)

    def brute_phrase(self, toks) -> np.ndarray:
        ids = [self.ref_pos.token_id(t) for t in toks]
        if any(i is None for i in ids):
            return np.zeros(0, dtype=np.int64)
        m = len(ids)
        s = self.stream
        return np.asarray([p for p in range(len(s) - m + 1)
                           if all(s[p + j] == ids[j] for j in range(m))],
                          dtype=np.int64)

    def brute_phrase_docs(self, toks) -> np.ndarray:
        pos = self.brute_phrase(toks)
        d = np.searchsorted(self.ref_pos.doc_starts, pos, side="right") - 1
        return np.unique(d)

    def brute_bm25(self, words, k: int) -> np.ndarray:
        """Independent BM25 top-k over the OR of ``words`` (float64,
        Lucene-style non-negative idf, ties by lowest doc id) — the
        reference every backend's ``rank<k>:`` answer must match."""
        k1, b = 1.2, 0.75
        n = len(self.term_lists)
        avgdl = sum(len(t) for t in self.term_lists) / max(1, n)
        scores = np.zeros(n)
        for w in dict.fromkeys(words):  # dedup: one contribution per term
            df = sum(1 for s in self.word_sets if w in s)
            if df == 0:
                continue  # unknown terms score nothing, query still answers
            idf = np.log1p((n - df + 0.5) / (df + 0.5))
            for d, terms in enumerate(self.term_lists):
                tf = terms.count(w)
                if tf:
                    dl = len(terms)
                    scores[d] += idf * tf * (k1 + 1) / (
                        tf + k1 * (1 - b + b * dl / avgdl))
        hit = np.nonzero(scores > 0)[0]
        order = sorted(hit.tolist(), key=lambda d: (-scores[d], d))
        return np.asarray(order[:k], dtype=np.int64)

    def brute_docs_topk(self, words, k: int) -> np.ndarray:
        docs = self.brute_docs(words)
        if len(docs) == 0:
            return docs
        scores = np.asarray([sum(self.tok_lists[d].count(w) for w in words)
                             for d in docs], dtype=np.int64)
        order = np.argsort(-scores, kind="stable")
        return docs[order][:k]

    def sample_queries(self, rng) -> list[tuple[str, np.ndarray]]:
        """(query string, brute reference) pairs drawn from the collection."""
        vocab = self.ref_np.vocab.id_to_token
        w = [vocab[int(rng.integers(len(vocab)))] for _ in range(6)]
        toks = self.tok_lists[int(rng.integers(len(self.docs)))]
        i = int(rng.integers(0, max(1, len(toks) - 3)))
        ph = toks[i : i + 2]
        ph3 = toks[i : i + 3]
        out = [
            (w[0], self.brute_docs([w[0]])),
            (f"{w[1]} {w[2]}", self.brute_docs([w[1], w[2]])),
            (f"{w[0]} {w[3]} {w[4]}", self.brute_docs([w[0], w[3], w[4]])),
            ('"' + " ".join(ph) + '"', self.brute_phrase(ph)),
            ('"' + " ".join(ph3) + '"', self.brute_phrase(ph3)),
            (f"top4: {w[1]} {w[2]}", self.brute_docs([w[1], w[2]])[:4]),
            (f"docs: {w[0]}", self.brute_docs([w[0]])),
            (f"docs: {w[1]} {w[2]}", self.brute_docs([w[1], w[2]])),
            ('docs: "' + " ".join(ph) + '"', self.brute_phrase_docs(ph)),
            (f"docs-top3: {w[1]} {w[2]}", self.brute_docs_topk([w[1], w[2]], 3)),
            ("docs: zzz-never-a-word", np.zeros(0, dtype=np.int64)),
            (f"rank4: {w[1]} {w[2]}", self.brute_bm25([w[1], w[2]], 4)),
            (f"rank3: {w[0]} {w[3]} {w[4]}",
             self.brute_bm25([w[0], w[3], w[4]], 3)),
            (f"rank5: {w[5]}", self.brute_bm25([w[5]], 5)),
            (f"rank4: {w[2]} zzz-never-a-word",
             self.brute_bm25([w[2], "zzz-never-a-word"], 4)),
        ]
        return out


def as_reference(q, got) -> np.ndarray:
    """An answer in the reference's form: word / AND / phrase hits as a
    sorted set (their order is the serving path's own), every other kind
    as returned."""
    got = np.asarray(got)
    if parse_query(q).kind in ("word", "and", "phrase"):
        got = np.sort(np.unique(got))
    return got

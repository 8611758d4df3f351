"""Packed-document training traffic, generated from a traffic file and a seed.

The document generator is the benchmark's own copy of the program's
synthetic corpus (``repro.data.pipeline.SyntheticCorpus``): Zipf-ish
document lengths and an order-1 Markov token chain (next = affine hash of
the current token, replaced by a uniform draw with probability
``markov_noise``).  It is vectorised over documents, so set-up makes every
document a run can use in well under a second, and the window measures
packing and the trainer rather than a per-token Python loop.

Every seed packs the same multiset of document lengths (drawn once from
the traffic file's ``length_seed``) in its own order, with its own tokens,
so seeds change the data and not the amount of work.

``Corpus`` is what the program's ``PackedLoader`` reads; ``pack_rows`` is
the benchmark's own packing of the same documents, which the reference
trains on: BOS, the document, an EOS gap; the loss mask covers BOS and the
document; labels are the next token.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

EOS = 0
BOS = 1
RESERVED = 2  # ids below this are EOS and BOS; documents use the rest

#: the program's loader asks its corpus for document ``row * ROW_STRIDE + k``
ROW_STRIDE = 1_000_003


def _length_pool(t: Dict, n: int) -> np.ndarray:
    """``n`` document lengths from the traffic file's fixed seed."""
    rng = np.random.default_rng(t["length_seed"])
    mean = t["mean_doc_len"]
    ln = rng.pareto(t["pareto_shape"], n) * mean * 0.5 + t["min_doc_len"]
    return np.clip(ln, t["min_doc_len"], t["max_doc_factor"] * mean
                   ).astype(np.int64)


def assign_rows(lengths: np.ndarray, seq_len: int, n_rows: int) -> np.ndarray:
    """First document of each row (and one past the last row's), packing
    documents in order until a row of ``seq_len + 1`` slots is full; a
    document that does not fit is cut, and the next row starts with the
    next document."""
    first = np.empty(n_rows + 1, np.int64)
    i = 0
    for r in range(n_rows):
        first[r] = i
        pos = 0
        while pos < seq_len:  # pos == seq_len leaves no room for a token
            if i >= len(lengths):
                raise IndexError("document pool exhausted")
            pos += min(int(lengths[i]), seq_len - pos) + 2
            i += 1
    first[n_rows] = i
    return first


class Documents:
    """All documents one run can pack, with their assignment to rows."""

    def __init__(self, t: Dict, vocab_size: int, seed: int, n_rows: int):
        S = t["seq_len"]
        per_row = (S + 1) / (t["mean_doc_len"] + 2)
        n = int(math.ceil(n_rows * per_row * 1.5)) + 64
        while True:
            lengths = np.random.default_rng([seed, 0]).permutation(
                _length_pool(t, n))
            try:
                self.first = assign_rows(lengths, S, n_rows)
                break
            except IndexError:
                n *= 2
        n_used = int(self.first[-1]) + 1  # the loader peeks one past a row
        self.lengths = lengths[:n_used]
        self.offsets = np.concatenate([[0], np.cumsum(self.lengths)])
        self.tokens = self._chains(t, vocab_size, seed)
        self.n_rows = n_rows
        self.seq_len = S

    def _chains(self, t: Dict, vocab_size: int, seed: int) -> np.ndarray:
        """One Markov chain per document, advanced in lock step."""
        rng = np.random.default_rng([seed, 1])
        n = len(self.lengths)
        V = vocab_size - RESERVED
        a = rng.integers(1, 257, n) * 2 + 1
        b = rng.integers(0, V, n)
        tok = rng.integers(0, V, n)
        width = int(self.lengths.max())
        noise = rng.integers(0, V, (width, n))
        pick = rng.random((width, n)) < t["markov_noise"]
        chains = np.empty((width, n), np.int64)
        for j in range(width):
            tok = np.where(pick[j], noise[j], (a * tok + b) % V)
            chains[j] = tok
        live = np.arange(width)[:, None] < self.lengths[None, :]
        return (chains.T[live.T] + RESERVED).astype(np.int32)

    def doc(self, i: int) -> np.ndarray:
        return self.tokens[self.offsets[i]:self.offsets[i + 1]]


class Corpus:
    """What ``PackedLoader`` reads: document ``k`` of row ``r`` is the
    ``k``-th document assigned to that row."""

    def __init__(self, docs: Documents):
        self.docs = docs

    def _doc(self, idx: int) -> np.ndarray:
        row, k = divmod(int(idx), ROW_STRIDE)
        if row >= self.docs.n_rows:
            raise IndexError(f"row {row} was not generated in set-up")
        return self.docs.doc(int(self.docs.first[row]) + k)


def pack_rows(docs: Documents, rows: range) -> Dict[str, np.ndarray]:
    """The reference's batch: rows packed from the documents assigned to
    them, independently of the program's loader."""
    S = docs.seq_len
    toks = np.full((len(rows), S + 1), EOS, np.int32)
    mask = np.zeros((len(rows), S + 1), np.float32)
    for out, r in enumerate(rows):
        pos = 0
        for i in range(int(docs.first[r]), int(docs.first[r + 1])):
            d = docs.doc(i)[:S - pos]
            toks[out, pos] = BOS
            toks[out, pos + 1:pos + 1 + len(d)] = d
            mask[out, pos:pos + 1 + len(d)] = 1.0
            pos += len(d) + 2
    return {"tokens": toks[:, :S], "labels": toks[:, 1:],
            "loss_mask": mask[:, 1:]}

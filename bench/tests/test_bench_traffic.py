"""The traffic generator: the program's loader over the benchmark's corpus
packs exactly the rows the reference packs on its own."""
import collections

import numpy as np
import pytest

from bench import traffic
from bench_tiny import TINY_TRAFFIC

MIXES = {
    "tiny": TINY_TRAFFIC,
    "seq2k": dict(TINY_TRAFFIC, rows=3, seq_len=2048, mean_doc_len=256,
                  min_doc_len=16),
    "long": dict(TINY_TRAFFIC, rows=1, seq_len=4096, mean_doc_len=1024,
                 min_doc_len=16),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 1])
def test_loader_rows_equal_reference_packing(mix, seed):
    from repro.data.pipeline import DataConfig, PackedLoader

    t = MIXES[mix]
    docs = traffic.Documents(t, 1000, seed, 4 * t["rows"])
    dc = DataConfig(vocab_size=1000, seq_len=t["seq_len"],
                    global_batch=t["rows"])
    loader = PackedLoader(dc, corpus=traffic.Corpus(docs))
    for step in range(4):
        got = loader.batch(step)
        want = traffic.pack_rows(docs, range(step * t["rows"],
                                             (step + 1) * t["rows"]))
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
        assert got["tokens"].min() >= 0 and got["tokens"].max() < 1000
        assert want["loss_mask"].mean() > 0.5


def test_seeds_share_the_lengths_in_another_order():
    t = MIXES["seq2k"]
    a = traffic.Documents(t, 1000, 1, 8)
    b = traffic.Documents(t, 1000, 2, 8)
    n = min(len(a.lengths), len(b.lengths)) - 16
    pool = traffic._length_pool(t, 10 ** 4)
    assert set(a.lengths) <= set(pool) and set(b.lengths) <= set(pool)
    assert not np.array_equal(a.lengths[:n], b.lengths[:n])
    assert not np.array_equal(a.tokens[:100], b.tokens[:100])


def test_same_seed_same_documents():
    t = MIXES["tiny"]
    a = traffic.Documents(t, 500, 7, 5)
    b = traffic.Documents(t, 500, 7, 5)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(a.first, b.first)


def test_rows_past_set_up_are_refused():
    t = MIXES["tiny"]
    docs = traffic.Documents(t, 500, 7, 2)
    with pytest.raises(IndexError):
        traffic.Corpus(docs)._doc(2 * traffic.ROW_STRIDE)


def test_chain_follows_the_affine_hash():
    """Outside the noise draws, each token is (a·prev + b) mod V."""
    t = dict(MIXES["tiny"], markov_noise=0.0)
    docs = traffic.Documents(t, 300, 3, 4)
    d = docs.doc(0).astype(np.int64) - traffic.RESERVED
    V = 300 - traffic.RESERVED
    steps = collections.Counter(
        ((d[i + 1] - d[i] * k) % V) for k in range(3, 515, 2)
        for i in range(len(d) - 1))
    assert steps.most_common(1)[0][1] >= len(d) - 1

"""The one traffic generator: documents, queries and the write schedule
of a cell, from its configuration file, its traffic file and the seed.

Every seed gets the same set of document lengths in another order, so
that seeds differ in content and not in the work they cause: the lengths
are the quantiles of the configuration's clipped log-normal, and every
aligned block of ``length_strata`` pool documents holds one length from
each stratum. A write batch is a whole number of such blocks, so each
has the same longest document and pads to the same sequence bucket.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


def doc_lengths(docs: dict, n: int) -> np.ndarray:
    """``n`` document lengths in words, ascending: the quantiles of a
    log-normal with this median and sigma, clipped to [min, max]."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    words = np.rint(docs["median_words"] * np.exp(docs["sigma"] * z))
    return np.clip(words, docs["min_words"], docs["max_words"]).astype(np.int64)


@dataclasses.dataclass
class Traffic:
    pool_texts: list[str]
    pool_words: np.ndarray  # [P] words per pool document
    own_words: np.ndarray  # [P, own] vocabulary ids of each document's own words
    vocab: list[str]
    key_order: np.ndarray  # [rows] the standing keys in the order they are replaced
    batch: int
    query_words: tuple[int, int]
    fresh_share: float
    fresh_window: int

    @property
    def pool_size(self) -> int:
        return len(self.pool_texts)

    def write_batch(self, b: int) -> tuple[list[int], np.ndarray]:
        """Batch ``b`` of the schedule: the standing keys it replaces and
        the pool documents whose text they get."""
        lo = b * self.batch
        if lo + self.batch > len(self.key_order):
            raise RuntimeError("the write schedule is exhausted: every standing key was replaced")
        keys = self.key_order[lo : lo + self.batch].tolist()
        docs = (lo + np.arange(self.batch)) % self.pool_size
        return keys, docs

    def query(self, rng: np.random.Generator, handed: int) -> tuple[str, int]:
        """One query and the pool document it is about. ``handed`` write
        batches have been handed over so far; a ``fresh_share`` of the
        queries ask about a document of the last ``fresh_window`` of them."""
        if handed > 0 and rng.random() < self.fresh_share:
            b = handed - 1 - int(rng.integers(0, min(self.fresh_window, handed)))
            doc = int((b * self.batch + rng.integers(0, self.batch)) % self.pool_size)
        else:
            doc = int(rng.integers(0, self.pool_size))
        n = int(rng.integers(self.query_words[0], self.query_words[1] + 1))
        own = self.own_words[doc]
        return " ".join(self.vocab[w] for w in own[rng.integers(0, len(own), n)]), doc


def make_traffic(config: dict, mix: dict, seed: int) -> Traffic:
    rng = np.random.default_rng([seed, 1])
    docs = config["documents"]
    pool, strata = int(config["pool_docs"]), int(docs["length_strata"])
    batch = int(mix["writer"]["batch"])
    if pool % strata or batch % strata or pool % batch:
        raise SystemExit(
            f"pool_docs {pool} and the writer's batch {batch} have to be multiples of "
            f"length_strata {strata}, and the pool a multiple of the batch"
        )
    blocks = pool // strata  # as many blocks as a stratum has lengths: one of each to a block
    by_stratum = doc_lengths(docs, pool).reshape(strata, blocks)
    words = np.empty((blocks, strata), np.int64)
    for s in range(strata):
        words[:, s] = by_stratum[s][rng.permutation(blocks)]
    words = rng.permuted(words, axis=1).reshape(-1)

    n_vocab, n_own = int(docs["vocab_words"]), int(docs["own_words"])
    vocab = [f"w{i:04d}" for i in range(n_vocab)]
    table = np.frombuffer("".join(w + " " for w in vocab).encode(), np.uint8).reshape(n_vocab, -1)
    own = rng.integers(0, n_vocab, (pool, n_own))
    total = int(words.sum())
    doc_of_word = np.repeat(np.arange(pool), words)
    word_ids = own[doc_of_word, rng.integers(0, n_own, total)]
    blob = table[word_ids].tobytes().decode()
    width = table.shape[1]
    ends = np.cumsum(words) * width
    texts = [blob[e - n * width : e - 1] for e, n in zip(ends.tolist(), words.tolist())]
    q = config["queries"]
    return Traffic(
        pool_texts=texts,
        pool_words=words,
        own_words=own,
        vocab=vocab,
        key_order=rng.permutation(int(config["rows"])),
        batch=batch,
        query_words=(int(q["min_words"]), int(q["max_words"])),
        fresh_share=float(mix["queries"]["fresh_share"]),
        fresh_window=int(mix["queries"]["fresh_window_batches"]),
    )

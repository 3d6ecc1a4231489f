"""WordPiece tokenizer (host-side, pure Python/NumPy).

Feeds the jit-batched encoders. Loads a standard BERT ``vocab.txt`` when
one is available locally; with no vocab (this image has no network
egress) it falls back to deterministic hashing of whitespace/punct
tokens into the vocab id space — embedding throughput and pipeline
semantics are unchanged, only absolute embedding quality needs the real
vocab + weights.
"""

from __future__ import annotations

import os
import re
import zlib

_BASIC = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")

CLS, SEP, PAD, UNK, MASK = 101, 102, 0, 100, 103


class WordPieceTokenizer:
    def __init__(
        self,
        vocab_file: str | None = None,
        vocab_size: int = 30522,
        lowercase: bool = True,
        max_input_chars_per_word: int = 100,
    ):
        self.vocab_size = vocab_size
        self.lowercase = lowercase
        self.max_chars = max_input_chars_per_word
        self.vocab: dict[str, int] | None = None
        self._vocab_file = vocab_file if vocab_file and os.path.exists(vocab_file) else None
        self._native = None  # lazy NativeTokenizer (C++ batched hot path)
        if self._vocab_file:
            with open(self._vocab_file, encoding="utf-8") as f:
                self.vocab = {line.rstrip("\n"): i for i, line in enumerate(f)}
        self.cls_id, self.sep_id, self.pad_id, self.unk_id = CLS, SEP, PAD, UNK
        if self.vocab is not None:
            self.cls_id = self.vocab.get("[CLS]", CLS)
            self.sep_id = self.vocab.get("[SEP]", SEP)
            self.pad_id = self.vocab.get("[PAD]", PAD)
            self.unk_id = self.vocab.get("[UNK]", UNK)

    def _word_ids(self, word: str) -> list[int]:
        if self.vocab is None:
            # stable hash into the non-special id range
            return [999 + zlib.crc32(word.encode()) % (self.vocab_size - 1000)]
        if len(word) > self.max_chars:
            return [self.unk_id]
        ids, start = [], 0
        while start < len(word):
            end, cur = len(word), None
            while start < end:
                sub = word[start:end]
                if start > 0:
                    sub = "##" + sub
                if sub in self.vocab:
                    cur = self.vocab[sub]
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]
            ids.append(cur)
            start = end
        return ids

    def encode(self, text: str, max_len: int = 128) -> list[int]:
        if self.lowercase:
            text = text.lower()
        ids = [self.cls_id]
        for word in _BASIC.findall(text):
            ids.extend(self._word_ids(word))
            if len(ids) >= max_len - 1:
                break
        ids = ids[: max_len - 1]
        ids.append(self.sep_id)
        return ids

    def batch_encode(self, texts, max_len: int = 128) -> list[list[int]]:
        """Tokenize many texts at once. ASCII texts run through the C++
        batched tokenizer (pn_tok_encode_batch — same ids as encode());
        others fall back to the per-text Python path. The pure-Python
        loop tops out near 50k texts/s, below one chip's embed rate, so
        the embed framework path depends on this."""
        m = self.batch_encode_matrix(texts, max_len)
        if m is None:
            return [self.encode(t, max_len=max_len) for t in texts]
        ids, lens = m
        return [ids[i, : lens[i]].tolist() for i in range(len(texts))]

    def batch_encode_matrix(self, texts, max_len: int = 128, *, stage=None):
        """Native-only zero-copy variant: -> (ids [n, max_len] int32,
        lens [n] int32) or None when the native path can't be used.
        Rows are pad_id-filled past their length — feedable straight
        into the encoder's bucketed batching without Python lists.

        Non-ASCII texts no longer abandon the C++ path for the whole
        batch: only those rows detour through the Python fallback (the
        C++ scanner is ascii-only, so parity needs Python lowercasing
        there), and their results are merged into the same matrix.

        ``stage``: an optional :class:`~pathway_tpu.ingest.HostIngestStage`
        — when given, the ASCII rows are encoded as parallel shard calls
        on the stage's workers (``pn_tok_encode_shard`` releases the
        GIL), each shard writing its disjoint row range of the shared
        output matrix. Same values at any worker count.
        """
        from .. import native as native_mod  # pathway_tpu.native

        if not native_mod.is_available():
            return None
        import numpy as np

        if self._native is None:
            self._native = native_mod.NativeTokenizer(
                self._vocab_file, self.vocab_size, self.lowercase, self.max_chars
            )
        texts = list(texts)
        ascii_rows = [i for i, t in enumerate(texts) if t.isascii()]
        if len(ascii_rows) == len(texts):
            if stage is not None and len(texts) >= 2:
                return self._encode_matrix_staged(texts, max_len, stage)
            return self._native.encode_batch(texts, max_len)
        n = len(texts)
        ids = np.full((n, max_len), self.pad_id, np.int32)
        lens = np.zeros(n, np.int32)
        if ascii_rows:
            sub = [texts[i] for i in ascii_rows]
            if stage is not None and len(sub) >= 2:
                sub_ids, sub_lens = self._encode_matrix_staged(sub, max_len, stage)
            else:
                sub_ids, sub_lens = self._native.encode_batch(sub, max_len)
            ids[ascii_rows] = sub_ids
            lens[ascii_rows] = sub_lens
        for i, t in enumerate(texts):
            if not t.isascii():
                row = self.encode(t, max_len=max_len)
                ids[i, : len(row)] = row
                lens[i] = len(row)
        return ids, lens

    def _encode_matrix_staged(self, texts, max_len: int, stage):
        """ASCII-only collaborative path: shard rows across the ingest
        stage's workers into one shared output matrix. Each shard call
        covers a disjoint row range, so the per-row values are exactly
        what one ``encode_batch`` call would produce."""
        import numpy as np

        n = len(texts)
        blob, offsets = self._native.prepare_blob(texts)
        out_ids = np.empty((n, max_len), np.int32)
        out_lens = np.empty(n, np.int32)
        shard = max(64, -(-n // max(1, stage.workers * 2)))
        spans = [(b, min(b + shard, n)) for b in range(0, n, shard)]

        def _run(span):
            b, e = span
            self._native.encode_shard(blob, offsets, b, e, max_len, out_ids, out_lens)
            return None

        for _ in stage.map_ordered(_run, spans):
            pass
        return out_ids, out_lens

    def encode_pair(self, a: str, b: str, max_len: int = 256) -> tuple[list[int], list[int]]:
        """(ids, token_type_ids) for cross-encoder input [CLS] a [SEP] b [SEP]."""
        if self.lowercase:
            a, b = a.lower(), b.lower()
        ia: list[int] = []
        for w in _BASIC.findall(a):
            ia.extend(self._word_ids(w))
        ib: list[int] = []
        for w in _BASIC.findall(b):
            ib.extend(self._word_ids(w))
        # truncate the longer side first (HF longest_first strategy)
        budget = max_len - 3
        while len(ia) + len(ib) > budget:
            if len(ia) >= len(ib):
                ia.pop()
            else:
                ib.pop()
        ids = [self.cls_id] + ia + [self.sep_id] + ib + [self.sep_id]
        tt = [0] * (len(ia) + 2) + [1] * (len(ib) + 1)
        return ids, tt


def default_tokenizer(model_dir: str | None = None, vocab_size: int = 30522) -> WordPieceTokenizer:
    """The vocabulary file of ``model_dir`` (or ``PATHWAY_TPU_VOCAB``)
    when there is one, else the seeded hash tokenizer. ``vocab_size`` is
    the rows of the embedding table the ids will index: an id past the
    table embeds to NaN without an error, so the seeded tokenizer is
    built at that size and a larger vocabulary file is refused."""
    candidates = []
    if model_dir:
        candidates.append(os.path.join(model_dir, "vocab.txt"))
    env = os.environ.get("PATHWAY_TPU_VOCAB")
    if env:
        candidates.append(env)
    for c in candidates:
        if os.path.exists(c):
            tok = WordPieceTokenizer(vocab_file=c)
            if len(tok.vocab) > vocab_size:
                raise ValueError(
                    f"{c} holds {len(tok.vocab)} tokens, the embedding table {vocab_size} rows"
                )
            return tok
    return WordPieceTokenizer(vocab_size=vocab_size)

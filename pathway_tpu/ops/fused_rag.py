"""Single-dispatch adaptive-RAG query pipeline.

The reference's RAG query path runs three host-driven stages — query
embedding (embedders.py:270), KNN retrieval
(external_integration/usearch_integration.rs:53), cross-encoder rerank
(rerankers.py:186) — each a separate model/native call. On TPU each
stage boundary costs a host->device dispatch and a sync, three per
query against the <50ms p50 SLO (BASELINE.md config 3).

Here the WHOLE query is one jit dispatch: tokenize on host, then
  encode query -> score vs HBM-resident doc matrix -> top-k ->
  gather doc TOKENS (also HBM-resident) -> build cross-encoder pairs
  on device -> cross-encoder forward -> final top-k
so the only host<->device traffic is the query token ids up and the
final (slot, score) pairs down.

Doc tokens live in a device [capacity, doc_seq] int32 store mirroring
the KNN index's slot assignment, maintained incrementally with the same
scatter discipline as the index matrix (ops/knn.py).
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np

from .knn import DeviceKnnIndex, _k_bucket

_NEG = -3.0e38


class FusedRagPipeline:
    """Docs in, answers out, one device dispatch per query batch.

    ``encoder``: SentenceEncoder (module/params/tokenizer exposed).
    ``cross``: CrossEncoderScorer, or None to skip reranking (then the
    query is encode -> top-k only, still one dispatch).
    """

    def __init__(
        self,
        encoder,
        cross=None,
        *,
        metric: str = "cos",
        reserved_space: int = 1024,
        doc_seq_len: int = 128,
        decoder=None,
    ):
        self.enc = encoder
        if cross is not None and not hasattr(cross, "module"):
            # a models.reranker.DeviceReranker (the rerank= knob's
            # object) carries its CrossEncoderScorer under .scorer
            scorer = getattr(cross, "scorer", None)
            if scorer is None or not hasattr(scorer, "module"):
                raise TypeError(
                    "cross must be a CrossEncoderScorer or DeviceReranker, "
                    f"got {type(cross).__name__}"
                )
            cross = scorer
        self.cross = cross
        self.doc_seq = doc_seq_len
        self.index = DeviceKnnIndex(
            dim=encoder.dim, metric=metric, reserved_space=reserved_space
        )
        self.texts: dict[Any, str] = {}
        pad = encoder.tokenizer.pad_id
        self._pad = pad
        self._tok_host = np.full((self.index.capacity, doc_seq_len), pad, np.int32)
        self._len_host = np.zeros((self.index.capacity,), np.int32)
        self._tok_dev = None
        self._len_dev = None
        self._tok_full = True
        self._tok_pending: dict[int, tuple[np.ndarray, int]] = {}
        self._jit_cache: dict[Any, Any] = {}
        self._dec_params = None
        self._dec_cfg = None
        if decoder is not None:
            self.set_decoder(decoder)

    def set_decoder(self, decoder, *, seed: int = 0) -> None:
        """Attach the generate stage. Accepts a ``DecoderConfig`` (params
        are initialised from ``seed``), a ``(params, config)`` tuple, a
        ``{"params": ..., "config": ...}`` dict, a ``DecodeEngine``
        (shares its weights), or ``True`` for the default geometry."""
        from ..decode.engine import DecoderConfig, init_decoder_params

        if decoder is True:
            decoder = DecoderConfig()
        if isinstance(decoder, DecoderConfig):
            self._dec_cfg = decoder
            self._dec_params = init_decoder_params(decoder, seed=seed)
        elif isinstance(decoder, tuple) and len(decoder) == 2:
            self._dec_params, self._dec_cfg = decoder
        elif isinstance(decoder, dict):
            self._dec_cfg = decoder["config"]
            self._dec_params = decoder.get("params")
            if self._dec_params is None:
                self._dec_params = init_decoder_params(self._dec_cfg, seed=seed)
        elif hasattr(decoder, "params") and hasattr(decoder, "model_cfg"):
            self._dec_params = decoder.params
            self._dec_cfg = decoder.model_cfg
        else:
            raise TypeError(
                f"decoder: cannot coerce {type(decoder).__name__} "
                "(want DecoderConfig, (params, config), dict, or DecodeEngine)"
            )
        # answer jits close over the decoder geometry — drop stale ones
        for key in [k for k in self._jit_cache if isinstance(k, tuple)]:
            del self._jit_cache[key]

    # ---- ingest ----

    def _doc_row(self, text: str) -> tuple[np.ndarray, int]:
        # doc part of a cross-encoder pair: wordpieces + [SEP]
        ids = self.enc.tokenizer.encode(text, self.doc_seq)[1:]  # drop [CLS]
        row = np.full((self.doc_seq,), self._pad, np.int32)
        row[: len(ids)] = ids
        return row, len(ids)

    def add_docs(self, keys: Sequence[Any], texts: Sequence[str]) -> None:
        embs = self.enc.encode_device(list(texts))
        self.index.add_batch_device(list(keys), embs)
        if self.index.capacity != len(self._tok_host):
            grown = np.full(
                (self.index.capacity, self.doc_seq), self._pad, np.int32
            )
            grown[: len(self._tok_host)] = self._tok_host
            self._tok_host = grown
            self._len_host = np.concatenate(
                [
                    self._len_host,
                    np.zeros((self.index.capacity - len(self._len_host),), np.int32),
                ]
            )
            self._tok_full = True  # device store re-uploads at new capacity
        for key, text in zip(keys, texts):
            self.texts[key] = text
            slot = self.index._slot_of[key]
            row, n = self._doc_row(text)
            self._tok_host[slot] = row
            self._len_host[slot] = n
            if not self._tok_full:
                self._tok_pending[slot] = (row, n)

    def remove_docs(self, keys: Sequence[Any]) -> None:
        for key in keys:
            self.index.remove(key)
            self.texts.pop(key, None)
        # token rows for freed slots are dead weight until overwritten

    def __len__(self) -> int:
        return len(self.index)

    # ---- device sync for the token store ----

    def _sync_tokens(self) -> None:
        import jax

        if self._tok_full or self._tok_dev is None:
            self._tok_dev = jax.device_put(self._tok_host)
            self._len_dev = jax.device_put(self._len_host)
            self._tok_full = False
            self._tok_pending.clear()
            return
        if not self._tok_pending:
            return
        if "tok_scatter" not in self._jit_cache:
            import jax.numpy as jnp
            from functools import partial

            @partial(jax.jit, donate_argnums=(0, 1))
            def tok_scatter(toks, lens, slots, rows, ns):
                toks = toks.at[slots].set(rows, mode="drop")
                lens = lens.at[slots].set(ns, mode="drop")
                return toks, lens

            self._jit_cache["tok_scatter"] = tok_scatter
        m = len(self._tok_pending)
        mb = _k_bucket(m)
        n_rows = self._tok_dev.shape[0]
        slots = np.full((mb,), n_rows, np.int32)
        rows = np.full((mb, self.doc_seq), self._pad, np.int32)
        ns = np.zeros((mb,), np.int32)
        for i, (slot, (row, n)) in enumerate(self._tok_pending.items()):
            slots[i], rows[i], ns[i] = slot, row, n
        self._tok_dev, self._len_dev = self._jit_cache["tok_scatter"](
            self._tok_dev, self._len_dev, slots, rows, ns
        )
        self._tok_pending.clear()

    # ---- query ----

    def _fused_body(self, use_cross: bool = True):
        """The pure (un-jitted) encode→retrieve→rerank trace, shared by
        the query jit and the answer jit's front half. ``use_cross=
        False`` builds the rerank-free variant (the decode plane's
        degrade path) even when a cross-encoder is configured."""
        cache_key = ("fused_body", use_cross)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        import jax
        import jax.numpy as jnp

        enc_mod = self.enc.module
        cross_mod = (
            self.cross.module if self.cross is not None and use_cross else None
        )
        l2 = self.index.metric == "l2"

        def fused(
            enc_params, cross_params, q_ids, q_lens, matrix, valid, toks, dlens, kr, kf
        ):
            Lq = q_ids.shape[1]
            qmask = jnp.arange(Lq)[None, :] < q_lens[:, None]
            emb = enc_mod.apply(enc_params, q_ids, qmask)  # [q, dim], L2-normed
            scores = emb @ matrix.T
            if l2:
                sq = jnp.sum(matrix * matrix, axis=1)
                scores = 2.0 * scores - sq[None, :] - 1.0
            scores = jnp.where(valid[None, :], scores, _NEG)
            rvals, ridx = jax.lax.top_k(scores, kr)  # [q, kr]
            if cross_mod is None:
                return ridx, rvals, ridx, rvals
            d_toks = toks[ridx]  # [q, kr, Ld]
            d_lens = dlens[ridx]  # [q, kr]
            nq, Ld = q_ids.shape[0], toks.shape[1]
            Lp = Lq + Ld
            pair = jnp.zeros((nq, kr, Lp), jnp.int32)
            pair = pair.at[:, :, :Lq].set(
                jnp.broadcast_to(q_ids[:, None, :], (nq, kr, Lq)).astype(jnp.int32)
            )

            def place(p_q, d_q, qlen):
                # docs start right after the query's [SEP]
                return jax.lax.dynamic_update_slice(p_q, d_q, (0, qlen))

            pair = jax.vmap(place)(pair, d_toks.astype(jnp.int32), q_lens)
            pos = jnp.arange(Lp)[None, None, :]
            tt = jnp.broadcast_to(
                pos >= q_lens[:, None, None], (nq, kr, Lp)
            ).astype(jnp.int32)
            pmask = pos < (q_lens[:, None] + d_lens)[:, :, None]
            flat = lambda x: x.reshape((nq * kr,) + x.shape[2:])
            cs = cross_mod.apply(
                cross_params, flat(pair), flat(pmask), flat(tt)
            ).reshape(nq, kr)
            # only reranked hits that were real retrievals stay alive
            cs = jnp.where(rvals > _NEG / 2, cs, _NEG)
            fvals, fidx = jax.lax.top_k(cs, kf)
            fslots = jnp.take_along_axis(ridx, fidx, axis=1)
            return fslots, fvals, ridx, rvals

        self._jit_cache[cache_key] = fused
        return fused

    def _fused_fn(self):
        if "fused" not in self._jit_cache:
            import jax
            from functools import partial

            self._jit_cache["fused"] = partial(
                jax.jit, static_argnames=("kr", "kf")
            )(self._fused_body())
        return self._jit_cache["fused"]

    def _answer_fn(self, max_new: int, use_cross: bool = True):
        """One jit for the WHOLE on-chip query path: encode query →
        retrieve → (cross-encoder rerank) → build generation prompt from
        the top hit's resident tokens → greedy decode. Between those
        stages nothing touches the host: doc tokens are gathered from
        the device store and spliced after the query in-trace, and the
        generate stage is ``decode.engine.decode_greedy`` vmapped over
        the query batch. Only token ids go up and (slots, scores,
        generated tokens) come down."""
        key = ("answer", max_new, use_cross)
        if key in self._jit_cache:
            return self._jit_cache[key]
        import jax
        import jax.numpy as jnp
        from functools import partial

        from ..decode.engine import decode_greedy

        body = self._fused_body(use_cross)
        dcfg = self._dec_cfg
        dec_max_prompt = dcfg.max_position - max_new
        if dec_max_prompt < 1:
            raise ValueError(
                f"answer: max_new={max_new} leaves no prompt room in "
                f"max_position={dcfg.max_position}"
            )

        @partial(jax.jit, static_argnames=("kr", "kf"))
        def answer(
            enc_params,
            cross_params,
            dec_params,
            q_ids,
            q_lens,
            matrix,
            valid,
            toks,
            dlens,
            kr,
            kf,
        ):
            fslots, fvals, _, _ = body(
                enc_params, cross_params, q_ids, q_lens, matrix, valid,
                toks, dlens, kr, kf,
            )
            nq, Lq = q_ids.shape
            Ld = toks.shape[1]
            top = fslots[:, 0]
            d_tok = toks[top].astype(jnp.int32)  # [q, Ld]
            d_len = dlens[top]
            buf = jnp.zeros((nq, Lq + Ld), jnp.int32)
            buf = buf.at[:, :Lq].set(q_ids.astype(jnp.int32))
            splice = lambda row, drow, qlen: jax.lax.dynamic_update_slice(
                row, drow, (qlen,)
            )
            buf = jax.vmap(splice)(buf, d_tok, q_lens)
            Lp = min(Lq + Ld, dec_max_prompt)
            prompt = buf[:, :Lp]
            # queries with no live hit generate from the query alone
            has_hit = fvals[:, 0] > _NEG / 2
            plen = jnp.clip(
                jnp.where(has_hit, q_lens + d_len, q_lens), 1, Lp
            ).astype(jnp.int32)
            gen = jax.vmap(
                lambda ids_row, ln: decode_greedy(
                    dec_params, dcfg, ids_row, ln, max_new
                )
            )(prompt, plen)
            return fslots, fvals, gen

        self._jit_cache[key] = answer
        return answer

    def _padded_queries(self, texts: Sequence[str], k_retrieve: int):
        """Tokenize/pad a query batch and sync device stores; returns
        (ids [qb, L], lens [qb], kr)."""
        m = self.enc.tokenizer.batch_encode_matrix(texts, self.enc.max_seq_len)
        if m is None:
            raise RuntimeError("fused RAG requires the matrix tokenizer path")
        ids_mat, lens = m
        self.index._sync()
        self._sync_tokens()
        from ..models.batching import DEFAULT_SEQ_BUCKETS, bucket

        n = len(texts)
        L = min(bucket(int(lens.max()), DEFAULT_SEQ_BUCKETS), ids_mat.shape[1])
        qb = _k_bucket(n)
        ids = np.zeros((qb, L), np.int32)
        ids[:n] = ids_mat[:, :L]
        lens_p = np.zeros((qb,), np.int32)
        lens_p[:n] = lens
        kr = min(_k_bucket(k_retrieve), self.index.capacity)
        return ids, lens_p, kr

    def _dispatch(self, texts: Sequence[str], k: int, k_retrieve: int):
        """Tokenize/pad and launch the fused kernel; returns the raw
        device (slots, scores) arrays without blocking."""
        from contextlib import nullcontext

        from ..internals.chip_ledger import CHIP_LEDGER

        texts = ["" if t is None else str(t) for t in texts]
        ids, lens_p, kr = self._padded_queries(texts, k_retrieve)
        # the fused kernel spans embed->retrieve->rerank in one XLA call,
        # so it books under the composite ``rag.fused`` account (the
        # per-plane split is unobservable inside a single dispatch);
        # syncing to read the clock is the accounting-mode tax, and it
        # costs overlap on the query_async path — accounting is opt-in
        chip = CHIP_LEDGER.on()
        with CHIP_LEDGER.timed("rag.fused") if chip else nullcontext():
            fslots, fvals, _, _ = self._fused_fn()(
                self.enc.live_params(),
                self.cross.params if self.cross is not None else None,
                ids,
                lens_p,
                self.index._dev_matrix,
                self.index._dev_valid,
                self._tok_dev,
                self._len_dev,
                kr=kr,
                kf=min(k, kr),
            )
            if chip:
                import jax

                jax.block_until_ready((fslots, fvals))
        return fslots, fvals

    def query_batch(
        self,
        texts: Sequence[str],
        k: int = 5,
        k_retrieve: int = 20,
    ) -> list[list[tuple[Any, float]]]:
        """Returns per query a list of (key, score) — reranked when a
        cross-encoder is configured, else raw retrieval scores."""
        if not len(texts) or len(self.index) == 0:
            return [[] for _ in texts]
        fslots, fvals = self._dispatch(texts, k, k_retrieve)
        fslots = np.asarray(fslots)
        fvals = np.asarray(fvals)
        out: list[list[tuple[Any, float]]] = []
        for qi in range(len(texts)):
            hits: list[tuple[Any, float]] = []
            for slot, val in zip(fslots[qi], fvals[qi]):
                if val <= _NEG / 2:
                    continue
                key = self.index._keys[slot]
                if key is None:
                    continue
                hits.append((key, float(val)))
            out.append(hits[:k])
        return out

    def query(self, text: str, k: int = 5, k_retrieve: int = 20):
        return self.query_batch([text], k, k_retrieve)[0]

    def query_async(self, text: str, k: int = 5, k_retrieve: int = 20):
        """Dispatch one fused query and return the raw device arrays
        (slots, scores) WITHOUT blocking — callers overlapping many
        queries pay the host->device link once, not per query. Resolve
        slots to keys with ``resolve`` once the arrays are ready."""
        return self._dispatch([text], k, k_retrieve)

    def answer_batch(
        self,
        texts: Sequence[str],
        k: int = 5,
        k_retrieve: int = 20,
        max_new: int = 16,
        rerank: bool = True,
    ) -> list[dict[str, Any]]:
        """The full on-chip query path: per query a dict with ``hits``
        (as :meth:`query_batch`) and ``tokens`` (``max_new`` greedy
        tokens from the decoder, conditioned on query + top hit). One
        device dispatch end to end — no host round-trips between the
        embed, retrieve, rerank and generate stages. ``rerank=False``
        is the degrade path: candidates keep retrieval order (the
        cross-encoder stage is skipped) but generation still runs."""
        if self._dec_params is None:
            raise RuntimeError(
                "fused RAG answer path needs a decoder "
                "(pass decoder= or call set_decoder)"
            )
        texts = ["" if t is None else str(t) for t in texts]
        if not len(texts):
            return []
        ids, lens_p, kr = self._padded_queries(texts, k_retrieve)
        use_cross = rerank and self.cross is not None
        fslots, fvals, gen = self._answer_fn(int(max_new), use_cross)(
            self.enc.live_params(),
            self.cross.params if use_cross else None,
            self._dec_params,
            ids,
            lens_p,
            self.index._dev_matrix,
            self.index._dev_valid,
            self._tok_dev,
            self._len_dev,
            kr=kr,
            kf=min(k, kr),
        )
        fslots = np.asarray(fslots)
        fvals = np.asarray(fvals)
        gen = np.asarray(gen)
        # generated answers inherit the retrieval staleness bound: the
        # tokens are conditioned on hits no staler than the index's
        # visible watermark at dispatch (key present only when the
        # freshness plane is live, so plane-off outputs are unchanged)
        from ..freshness.plane import FRESHNESS

        bound = (
            FRESHNESS.observe_answer(self.index) if FRESHNESS.active() else None
        )
        out: list[dict[str, Any]] = []
        for qi in range(len(texts)):
            hits: list[tuple[Any, float]] = []
            for slot, val in zip(fslots[qi], fvals[qi]):
                if val <= _NEG / 2:
                    continue
                key = self.index._keys[slot]
                if key is None:
                    continue
                hits.append((key, float(val)))
            row: dict[str, Any] = {
                "hits": hits[:k],
                "tokens": [int(t) for t in gen[qi]],
            }
            if bound is not None:
                row["freshness_ms"] = round(bound["staleness_ms"], 3)
            out.append(row)
        return out

    def answer(self, text: str, **kw) -> dict[str, Any]:
        return self.answer_batch([text], **kw)[0]

    def resolve(self, fslots, fvals, k: int = 5) -> list[tuple[Any, float]]:
        fslots = np.asarray(fslots)[0]
        fvals = np.asarray(fvals)[0]
        hits = []
        for slot, val in zip(fslots, fvals):
            if val <= _NEG / 2:
                continue
            key = self.index._keys[slot]
            if key is not None:
                hits.append((key, float(val)))
        return hits[:k]

"""SentenceEncoder / CrossEncoder — the TPU replacements for the
reference's torch hot paths.

Reference: sentence-transformers ``model.encode`` per row inside a sync
UDF (/root/reference/python/pathway/xpacks/llm/embedders.py:270-329) and
``CrossEncoder.predict`` (rerankers.py:186). Here: tokenize on host,
pad to bucketed static shapes, run one jit-compiled bf16 forward per
bucket (cached), optionally pjit over a device mesh for data-parallel
embedding.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..tracing import (
    TRACING_METRICS,
    dispatched as _dispatched,
    span as _span,
    tracing_enabled as _tracing_enabled,
)
from .batching import DEFAULT_SEQ_BUCKETS, chunks, pad_token_batch
from .encoder import (
    CrossEncoderHead,
    EncoderConfig,
    TextEncoder,
    init_params,
    load_hf_weights,
)
from .hybrid_ssm import HybridSSMConfig, HybridSSMEncoder
from .latent_moe import LatentMoEConfig, LatentMoEEncoder
from .power_retention import PowerRetentionConfig, PowerRetentionEncoder
from .tokenizer import WordPieceTokenizer, default_tokenizer

#: model name -> the configuration it stands for; a name that is not
#: here is served by the default all-MiniLM-L6-v2 block
ARCHITECTURES = {
    "all-minilm-l6-v2": EncoderConfig.minilm_l6,
    "all-minilm-l12-v2": EncoderConfig.minilm_l12,
    "ai21-jamba2-3b": HybridSSMConfig.jamba2_3b,
    # no published model: both kinds of hybrid layer at test widths
    "hybrid-ssm-tiny-for-tests": HybridSSMConfig.tiny_for_tests,
    # one chip's share of the published model: 16 of 256 experts a
    # layer, 1 + 4 of its 3 + 58 layers, an eighth of its vocabulary
    "openpangu-ultra-moe-718b.ep16-l5": LatentMoEConfig.pangu_ultra_moe_ep16_l5,
    # no published model: a dense and two sparse-expert layers at test widths
    "latent-moe-tiny-for-tests": LatentMoEConfig.tiny_for_tests,
    # the first pipeline stage of the published model: the embedding and 8 of its 40 layers
    "brumby-14b-base.l8": PowerRetentionConfig.brumby_14b_base_l8,
    # no published model: two power-retention layers, heads grouped 5 : 1, at test widths
    "power-retention-tiny-for-tests": PowerRetentionConfig.tiny_for_tests,
}

#: the modules that are not the flax BERT block: they make their own
#: parameter tree, leaf by leaf in its final types, and say how many
#: tokens a dispatch group may hold
_OWN_MODULES = {
    HybridSSMConfig: HybridSSMEncoder,
    LatentMoEConfig: LatentMoEEncoder,
    PowerRetentionConfig: PowerRetentionEncoder,
}


def _param_shapes(module):
    """``module.param_kinds()`` as a tree of ``jax.ShapeDtypeStruct``: what
    ``module.init`` would make, and no array."""
    return jax.tree_util.tree_map(
        lambda kind: jax.ShapeDtypeStruct(kind[0], jnp.dtype(kind[1])),
        module.param_kinds(),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def architecture_of(model: str):
    """The configuration ``model`` stands for, with or without its
    organisation ("ai21labs/AI21-Jamba2-3B"), in any case."""
    return ARCHITECTURES.get(model.rsplit("/", 1)[-1].lower(), EncoderConfig.minilm_l6)()


def _checkpoint_or_seeded(params, checkpoint_dir: str | None):
    """The weights in ``checkpoint_dir`` when that directory exists,
    the seeded ``params`` when it does not. A directory that is there
    and does not load raises: a run must never score with random
    weights while believing it read a checkpoint."""
    if checkpoint_dir and os.path.isdir(checkpoint_dir):
        return load_hf_weights(params, checkpoint_dir)
    return params


class SentenceEncoder:
    """Batched text -> L2-normalized embeddings [n, hidden]."""

    def __init__(
        self,
        model: str = "all-MiniLM-L6-v2",
        *,
        config: EncoderConfig | None = None,
        checkpoint_dir: str | None = None,
        max_seq_len: int | None = None,
        max_batch: int = 1024,
        seed: int = 0,
        mesh=None,
        data_axis: str = "data",
    ):
        if config is None:
            config = architecture_of(model)
        self.model_name = model
        if max_seq_len is None:
            # a module that takes documents whole says how long one may be
            max_seq_len = getattr(config, "max_seq_len", 256)
        elif hasattr(config, "max_seq_len"):
            # and is built for the length it is given: what its attention
            # looks back over, what it refuses as too long for a stream
            config = dataclasses.replace(config, max_seq_len=max_seq_len)
        self.cfg = config
        self.max_seq_len = max_seq_len
        self.max_batch = max_batch
        checkpoint_dir = checkpoint_dir or os.environ.get("PATHWAY_TPU_CKPT")
        self._seed = seed
        if type(config) in _OWN_MODULES:
            if checkpoint_dir and os.path.isdir(checkpoint_dir):
                raise NotImplementedError(
                    f"no checkpoint loader for the parameter tree of {model!r}: "
                    f"{checkpoint_dir} would be ignored and seeded weights scored in its place"
                )
            if mesh is not None:
                raise NotImplementedError(f"the encoder of {model!r} has no mesh path")
            self.module = _OWN_MODULES[type(config)](config)
            # a dispatch group is bounded by tokens, not by rows: the
            # module says how many it lets be alive at once
            self.max_batch = min(max_batch, max(8, config.max_group_tokens // max_seq_len))
            # shapes and types until a forward needs values: whoever
            # brings weights of their own (a loader, the benchmark) lays
            # them over this tree, and a second copy never exists
            self.params = _param_shapes(self.module)
            self.tokenizer = WordPieceTokenizer(vocab_size=config.vocab_size)
        else:
            self.module = TextEncoder(config)
            self.params = _checkpoint_or_seeded(
                init_params(self.module, config, seed=seed), checkpoint_dir
            )
            self.tokenizer = default_tokenizer(checkpoint_dir, config.vocab_size)
        # every sequence bucket is a compiled program of the whole model:
        # a module whose program is expensive names fewer
        self._seq_buckets = getattr(config, "seq_buckets", DEFAULT_SEQ_BUCKETS)
        self.mesh = mesh
        self.data_axis = data_axis
        fwd = self.module.apply
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.params = jax.device_put(
                self.params, NamedSharding(mesh, P())
            )
            self._data_sharding = NamedSharding(mesh, P(data_axis))
            # data-parallel by shard_map, not GSPMD: on TPU the module's
            # attention is a Mosaic kernel, which XLA cannot partition
            # automatically. check_vma off: pallas_call's out_shape
            # carries no vma annotation
            fwd = jax.shard_map(
                fwd,
                mesh=mesh,
                in_specs=(P(), P(data_axis), P(data_axis)),
                out_specs=P(data_axis),
                check_vma=False,
            )
        else:
            self._data_sharding = None
        # profiled jit: reports the compile-vs-execute split to an
        # active RunProfiler (no-op outside pw.run(profile=...) runs)
        from ..internals.profiler import wrap_jit

        self._fwd = wrap_jit("sentence_encoder.fwd", jax.jit(fwd))
        # donated double-buffer ring for the wire id/length uploads of
        # the shared group forward (lazy; engine/device_ring.py)
        self._wire_ring = None
        from ..internals.ledger import LEDGER, pytree_nbytes

        LEDGER.update(
            "weights", f"encoder:{model}", pytree_nbytes(self.params)
        )

    @property
    def dim(self) -> int:
        return self.cfg.hidden_size

    def live_params(self):
        """The parameter tree with values: the seeded leaves are made,
        leaf by leaf, the first time a forward asks and nobody assigned
        a tree of arrays in the meantime."""
        leaf = self.params
        while isinstance(leaf, dict) and leaf:
            leaf = next(iter(leaf.values()))
        if isinstance(leaf, jax.ShapeDtypeStruct):
            self.params = self.module.init(self._seed)
        return self.params

    def jit_cache_size(self) -> int:
        """Distinct compiled entries in the forward jit's cache — the
        ground truth the deep verifier's recompilation predictor
        (``models.batching.predict_compile_keys`` / PWL018) is
        validated against in the bucket-sweep test."""
        inner = getattr(self._fwd, "__wrapped__", self._fwd)
        cache_size = getattr(inner, "_cache_size", None)
        return int(cache_size()) if cache_size is not None else -1

    def predict_compile_keys(self, lengths) -> set[tuple[int, int]]:
        """The (B, S) jit keys ``encode_tokens`` would compile for a
        workload of token ``lengths`` at this encoder's geometry."""
        from .batching import predict_compile_keys

        ndata = self.mesh.shape[self.data_axis] if self.mesh is not None else 1
        return predict_compile_keys(
            lengths, seq_buckets=self._seq_buckets, max_batch=self.max_batch, mesh_ndata=ndata
        )

    def _run_padded(self, ids, mask):
        if self._data_sharding is not None:
            ndata = self.mesh.shape[self.data_axis]
            pad = (-ids.shape[0]) % ndata
            if pad:
                ids = np.concatenate([ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
                mask = np.concatenate([mask, np.zeros((pad, mask.shape[1]), bool)])
            ids = jax.device_put(ids, self._data_sharding)
            mask = jax.device_put(mask, self._data_sharding)
        return self._fwd(self.live_params(), ids, mask)

    def encode_tokens(self, toks: Sequence[list[int]], as_numpy: bool = True):
        """Embed pre-tokenized sequences. Dispatch is async: all buckets
        are enqueued before the first result is pulled, so host padding
        overlaps device compute."""
        if not len(toks):
            return np.zeros((0, self.dim), np.float32)
        # order by length so buckets stay dense, then restore order
        order = sorted(range(len(toks)), key=lambda i: len(toks[i]))
        batch = self.max_batch
        if self.mesh is not None:
            ndata = self.mesh.shape[self.data_axis]
            batch = max(batch - batch % ndata, ndata)
        pending = []
        for group in chunks(order, batch):
            ids, mask, _, n = pad_token_batch(
                [toks[i] for i in group], pad_id=self.tokenizer.pad_id,
                max_batch=batch,
            )
            pending.append((group, n, self._run_padded(ids, mask)))
        out = np.empty((len(toks), self.dim), np.float32)
        for group, n, emb in pending:
            out[np.asarray(group)] = np.asarray(emb)[:n]
        return out

    def encode(self, texts: Sequence[str]) -> np.ndarray:
        if not len(texts):
            return np.zeros((0, self.dim), np.float32)
        texts = ["" if t is None else str(t) for t in texts]
        m = self._tokenize_matrix(texts)
        if m is None:  # no native lib
            toks = [self.tokenizer.encode(t, self.max_seq_len) for t in texts]
            return self.encode_tokens(toks)
        return self._encode_matrix(*m)

    def _tokenize_matrix(self, texts):
        """Tokenize through the collaborative host-ingest stage when one
        is configured (PATHWAY_INGEST_WORKERS / pw.run(ingest_workers=)),
        else inline. Values are identical either way; the stage only
        parallelizes the GIL-released native shard calls and records the
        short/long routing split for the seq buckets."""
        from ..ingest import stage as ingest_stage

        st = ingest_stage.get_stage()
        with _span("embed_tokenize", rows=len(texts)) as sp:
            m = self.tokenizer.batch_encode_matrix(texts, self.max_seq_len, stage=st)
            if sp is not None and m is not None:
                sp.attrs["tokens"] = int(m[1].sum())  # real tokens, no padding
        if m is not None and st is not None:
            from ..ingest.stage import route_by_length
            from .batching import DEFAULT_SEQ_BUCKETS, bucket

            # short = fits the seq bucket at half this encoder's window;
            # the argsorted group packer keeps the two populations in
            # separate dense buckets, so one long straggler no longer
            # pads out a batch of short docs
            threshold = bucket(max(1, self.max_seq_len // 2), DEFAULT_SEQ_BUCKETS)
            route_by_length(m[1].tolist(), threshold)
        return m

    def _matrix_groups(self, ids_mat: np.ndarray, lens: np.ndarray):
        """Bucketed dispatch straight from the native tokenizer's padded
        ids matrix — no per-row Python lists on the hot path. Yields
        (group_indices, n_real, device_embeddings)."""
        from .batching import DEFAULT_BATCH_BUCKETS, bucket

        if hasattr(self.module, "apply_stream"):
            return self._stream_groups(ids_mat, lens)
        n = len(lens)
        order = np.argsort(lens, kind="stable")  # dense length buckets
        batch = self.max_batch
        if self.mesh is not None:
            ndata = self.mesh.shape[self.data_axis]
            batch = max(batch - batch % ndata, ndata)
        pending = []
        for start in range(0, n, batch):
            group = order[start : start + batch]
            ng = len(group)
            with _span("embed_pack", rows=ng):
                L = min(
                    bucket(int(lens[group].max()), self._seq_buckets),
                    ids_mat.shape[1],
                )
                ids = np.take(ids_mat[:, :L], group, axis=0)
                if ng < batch:
                    bb = tuple(b for b in DEFAULT_BATCH_BUCKETS if b < batch) + (batch,)
                    B = max(bucket(ng, bb), ng)
                    if B > ng:
                        ids = np.pad(ids, ((0, B - ng), (0, 0)))
            if self.mesh is None:
                # one (B, L)-shaped jit serves every batch size
                ln = np.zeros((ids.shape[0],), np.int32)
                ln[:ng] = lens[group]
                pending.append((group, ng, self._run_group(ids, ln)))
            else:
                mask = np.arange(ids.shape[1])[None, :] < np.concatenate(
                    [lens[group], np.zeros(ids.shape[0] - ng, lens.dtype)]
                )[:, None]
                pending.append((group, ng, self._run_padded(ids, mask)))
        return pending

    def _stream_groups(self, ids_mat: np.ndarray, lens: np.ndarray):
        """Dispatch bounded by tokens, for a module that packs: the
        documents, in the order they came, go to the device as token
        streams of ``max_group_tokens`` — as many whole documents a
        stream as fit, each padded to ``doc_align`` only — with where
        each starts and how long it is beside them. One compiled
        program, whatever the batch: it computes the stream's live
        chunks. Yields what :meth:`_matrix_groups` yields."""
        cfg = self.cfg
        t, align = cfg.max_group_tokens, cfg.doc_align
        most = t // align  # documents of one stream
        padded = -(-lens.astype(np.int64) // align) * align
        ends = np.cumsum(padded)  # in one endless stream
        begins = ends - padded
        pending, lo = [], 0
        while lo < len(lens):
            hi = int(np.searchsorted(ends, begins[lo] + t, side="right"))
            hi = min(max(hi, lo + 1), lo + most)
            with _span("embed_pack", rows=hi - lo):
                starts = np.full((most,), t, np.int32)
                starts[: hi - lo] = begins[lo:hi] - begins[lo]
                doc_lens = np.zeros((most,), np.int32)
                doc_lens[: hi - lo] = lens[lo:hi]
                ids = np.zeros((t,), np.int32)
                for at, i in zip(starts, range(lo, hi)):
                    ids[at : at + lens[i]] = ids_mat[i, : lens[i]]
            pending.append((np.arange(lo, hi), hi - lo, self._run_stream(ids, starts, doc_lens)))
            lo = hi
        return pending

    def _ring(self):
        """The donated ring the wire arrays of a dispatch stage through
        (depth 2 by default, PATHWAY_WIRE_RING_DEPTH to deepen)."""
        if self._wire_ring is None:
            from ..engine.device_ring import DeviceRing

            depth = max(2, int(os.environ.get("PATHWAY_WIRE_RING_DEPTH", "2")))
            self._wire_ring = DeviceRing(depth=depth, name="sentence_encoder.wire")
        return self._wire_ring

    def _run_stream(self, ids: np.ndarray, starts: np.ndarray, lens: np.ndarray):
        """The compiled forward of a module that packs: one stream of
        token ids, its documents' starts and lengths -> their rows."""
        from ..internals.profiler import ENCODER_KERNEL_STATS, wrap_jit

        if getattr(self, "_fwd_stream", None) is None:
            self._fwd_stream = wrap_jit("sentence_encoder.fwd_stream", jax.jit(self.module.apply_stream))
        cfg = self.cfg
        real = int(lens.sum())
        live = int((starts + lens)[lens > 0].max()) if real else 0
        computed = -(-live // cfg.token_chunk) * cfg.token_chunk
        if _tracing_enabled():
            # the module's own stage, counted here from the lengths the
            # host has: nothing is fetched from the device
            stage, calls, units = cfg.stream_counts(lens, computed)
            for _ in range(calls):
                TRACING_METRICS.observe(stage, 0.0, "", units=units)
        ENCODER_KERNEL_STATS.record_dispatch(
            seq=cfg.max_group_tokens,  # the stream: what it leaves dead is what it skips
            batch=1,
            real_tokens=real,
            computed_tokens=computed,
            flops=float(sum(int(n) * cfg.flops_per_token(int(n)) for n in lens if n)),
        )
        with _span("embed_dispatch", rows=int((lens > 0).sum()), tokens=computed):
            wire = self._ring().stage([ids, starts, lens])
            out = self._fwd_stream(self.live_params(), *wire)
            _dispatched(out)
            self._wire_ring.retire(wire)
        return out

    def _fused_layer_ok(self, seq_len: int) -> bool:
        """Route the inference jit through the whole-layer pallas kernel
        (ops/fused_layer.py) — measured 1.4-1.5x over the per-op XLA
        lowering at MiniLM geometry on v5e (59 -> 88 TF at S=160)."""
        from ..ops.fused_layer import use_fused_encoder

        return use_fused_encoder(self.cfg, seq_len)

    def _run_group(self, ids: np.ndarray, lens: np.ndarray):
        """The one non-mesh compiled forward: (B, L) int ids + lengths
        (mask built on device)."""
        import jax
        import jax.numpy as jnp

        if getattr(self, "_fwd_group", None) is None:

            def fwd_group(p, ids_, lens_):
                mask = jnp.arange(ids_.shape[1])[None, :] < lens_[:, None]
                ids32 = ids_.astype(jnp.int32)
                if self._fused_layer_ok(ids_.shape[1]):
                    from ..ops.fused_layer import (
                        encoder_forward,
                        fused_encoder_interpret,
                    )

                    # lens feed the ragged kernel grid directly: the
                    # per-block lengths mask padded keys in-kernel and
                    # let all-padding blocks be skipped, not computed
                    return encoder_forward(
                        p,
                        self.cfg,
                        ids32,
                        mask,
                        lens=lens_.astype(jnp.int32),
                        interpret=fused_encoder_interpret(self.cfg),
                    )
                # a module with sparse experts also says how many real
                # tokens each held expert got: (rows, loads)
                return getattr(self.module, "apply_with_loads", self.module.apply)(p, ids32, mask)

            from ..internals.profiler import wrap_jit

            self._fwd_group = wrap_jit(
                "sentence_encoder.fwd_group", jax.jit(fwd_group)
            )
        # int16 halves the host->device id bytes; only when ids fit.
        # The wire arrays stage through a donated ring (depth 2 by
        # default, PATHWAY_WIRE_RING_DEPTH to deepen): the device_put is
        # non-blocking (the upload overlaps whatever compute is still in
        # flight) and slot reuse donates the previous group's buffers
        # instead of accumulating one upload per dispatch in HBM.
        wire = np.int16 if self.cfg.vocab_size < 32768 else np.int32
        computed = self._record_dispatch(ids.shape[0], ids.shape[1], lens)
        group_counts = getattr(self.cfg, "group_counts", None)
        if _tracing_enabled() and group_counts is not None and (counted := group_counts(ids.shape[1], lens)):
            # the module's own stage, counted from the shape and lengths
            # the host has: nothing is fetched from the device
            stage, calls, units = counted
            for _ in range(calls):
                TRACING_METRICS.observe(stage, 0.0, "", units=units)
        with _span("embed_dispatch", rows=ids.shape[0], tokens=computed):
            ids_dev, lens_dev = self._ring().stage(
                [ids.astype(wire, copy=False), lens.astype(np.int32, copy=False)]
            )
            from ..internals.chip_ledger import CHIP_LEDGER

            if CHIP_LEDGER.on():
                # chip-time accounting syncs the dispatch to read the clock
                # (the opt-in trade: exact encode device-seconds for lost
                # dispatch pipelining); jit compiles nested in this window
                # book under `compile`, not here
                with CHIP_LEDGER.timed("encode"):
                    out = self._fwd_group(self.live_params(), ids_dev, lens_dev)
                    jax.block_until_ready(out)
            else:
                out = self._fwd_group(self.live_params(), ids_dev, lens_dev)
            _dispatched(out[0] if isinstance(out, tuple) else out)
            self._wire_ring.retire([ids_dev, lens_dev])
        if isinstance(out, tuple):
            out, loads = out
            if _tracing_enabled():
                # the device's own count, handed over un-fetched: whoever
                # reads the stage totals pays the transfer, not this path
                TRACING_METRICS.owe_expert_loads("embed_experts", loads)
        return out

    def _record_dispatch(self, batch: int, seq: int, lens: np.ndarray) -> int:
        """MFU / pad-waste attribution for one group dispatch, counted
        as it is issued (feeds the dashboard column, the
        pathway_encoder_* gauges and the kernel.dispatch flight-recorder
        events). -> the token rows the compiled forward computes for the
        group: what the whole-layer kernel's tile rule leaves of
        ``batch * seq``, every row of the program for a module that
        skips nothing."""
        from ..internals.profiler import ENCODER_KERNEL_STATS

        computed = batch * seq
        own_flops = getattr(self.cfg, "flops_per_token", None)
        if own_flops is not None:
            # a module that counts its own work computes every row of
            # the program, padding included
            per_token = own_flops(seq)
        elif self._fused_layer_ok(seq):
            from ..ops.fused_layer import _pack_rows, computed_tokens, encoder_flops_per_token

            computed = computed_tokens(lens, seq)
            batch += (-batch) % _pack_rows(seq)  # kernel pads rows to p-multiples
            per_token = encoder_flops_per_token(self.cfg, seq)
        else:
            return computed  # the per-op XLA lowering of a BERT block: no gauge reads it
        ENCODER_KERNEL_STATS.record_dispatch(
            seq=seq,
            batch=batch,
            real_tokens=int(lens.sum()),
            computed_tokens=computed,
            flops=computed * per_token,
        )
        return computed

    def _encode_matrix(self, ids_mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
        out = np.empty((len(lens), self.dim), np.float32)
        for group, ng, emb in self._matrix_groups(ids_mat, lens):
            out[group] = np.asarray(emb)[:ng]
        return out

    def encode_device(self, texts: Sequence[str], pad_to: int | None = None):
        """texts -> embeddings as a DEVICE-resident [n, dim] jax array
        in input order. The streaming pipeline's TPU-native hot path:
        embeddings feed the on-device KNN index directly, so they never
        round-trip through host memory. Token ids ship as
        int16 and masks are built on device from lengths — halves the
        host->device bytes on the ingest path.

        ``pad_to``: pad the output to [pad_to, dim] (extra rows zero)
        AND keep every intermediate shape at its bucket size, so
        varying batch sizes hit a bounded set of compiled programs —
        streaming epochs have arbitrary sizes and must not recompile
        the ingest chain per size."""
        import jax.numpy as jnp

        if not len(texts):
            return jnp.zeros((pad_to or 0, self.dim), jnp.float32)
        texts = ["" if t is None else str(t) for t in texts]
        # tokenize/compute overlap: split large batches in half — the
        # first half's dispatch is async, so the second half tokenizes
        # on the host while the device crunches the first
        if pad_to is None and len(texts) >= 4 * self.max_batch:
            mid = (len(texts) // 2 // self.max_batch) * self.max_batch
            if mid and len(texts) - mid >= 2 * self.max_batch:
                first = self.encode_device(texts[:mid])
                second = self.encode_device(texts[mid:])
                return jnp.concatenate([first, second], axis=0)
        m = self._tokenize_matrix(texts)
        return self._dispatch_tokenized(texts, m, pad_to)

    def _dispatch_tokenized(self, texts, m, pad_to: int | None = None):
        """Device tail of :meth:`encode_device`: bucket-pack an already
        tokenized matrix and dispatch the shared group forward."""
        import jax.numpy as jnp

        if m is None:
            embs = jnp.asarray(self.encode(texts))
            if pad_to and pad_to > embs.shape[0]:
                embs = jnp.concatenate(
                    [embs, jnp.zeros((pad_to - embs.shape[0], self.dim), jnp.float32)]
                )
            return embs
        ids_mat, lens = m
        n_out = pad_to or len(lens)
        pending = self._matrix_groups(ids_mat, lens)
        with _span("embed_gather", rows=len(lens)):  # the eager tail: the groups' rows back in input order
            if pad_to:
                # keep full bucket-shaped group outputs; rows past each
                # group's real count scatter out of bounds and drop
                embs = jnp.concatenate([emb for _, _, emb in pending], axis=0)
                order = np.full((int(embs.shape[0]),), n_out, np.int64)
                off = 0
                for group, ng, emb in pending:
                    order[off : off + ng] = group
                    off += int(emb.shape[0])
            else:
                # a full group is taken as it is: no slice op per group
                embs = jnp.concatenate(
                    [emb if ng == emb.shape[0] else emb[:ng] for _, ng, emb in pending], axis=0
                )
                order = np.concatenate([group for group, _, _ in pending])
            out = jnp.zeros((n_out, self.dim), jnp.float32)
            out = out.at[jnp.asarray(order)].set(embs.astype(jnp.float32), mode="drop")
            _dispatched(out)
        return out

    def __call__(self, texts: Sequence[str]) -> np.ndarray:
        return self.encode(texts)


class CrossEncoderScorer:
    """Batched (query, doc) pairs -> relevance scores [n]."""

    def __init__(
        self,
        model: str = "ms-marco-MiniLM-L-6-v2",
        *,
        config: EncoderConfig | None = None,
        checkpoint_dir: str | None = None,
        max_seq_len: int = 256,
        max_batch: int = 256,
        seed: int = 0,
    ):
        self.cfg = config or EncoderConfig.cross_encoder_l6()
        self.model_name = model
        self.max_seq_len = max_seq_len
        self.max_batch = max_batch
        self.module = CrossEncoderHead(self.cfg)
        checkpoint_dir = checkpoint_dir or os.environ.get("PATHWAY_TPU_XENC_CKPT")
        self.params = _checkpoint_or_seeded(
            init_params(self.module, self.cfg, seed=seed), checkpoint_dir
        )
        self.tokenizer = default_tokenizer(checkpoint_dir, self.cfg.vocab_size)
        from ..internals.profiler import wrap_jit

        self._fwd = wrap_jit("cross_encoder.fwd", jax.jit(self.module.apply))
        from ..internals.ledger import LEDGER, pytree_nbytes

        LEDGER.update(
            "weights", f"reranker:{model}", pytree_nbytes(self.params)
        )

    def score(self, pairs: Sequence[tuple[str, str]]) -> np.ndarray:
        if not len(pairs):
            return np.zeros((0,), np.float32)
        enc = [self.tokenizer.encode_pair(a or "", b or "", self.max_seq_len) for a, b in pairs]
        toks = [e[0] for e in enc]
        tts = [e[1] for e in enc]
        order = sorted(range(len(toks)), key=lambda i: len(toks[i]))
        out = np.empty((len(toks),), np.float32)
        for group in chunks(order, self.max_batch):
            ids, mask, tt, n = pad_token_batch(
                [toks[i] for i in group],
                pad_id=self.tokenizer.pad_id,
                max_batch=self.max_batch,
                token_type_lists=[tts[i] for i in group],
            )
            scores = np.asarray(self._fwd(self.params, ids, mask, tt))[:n]
            out[np.asarray(group)] = scores
        return out

    def __call__(self, pairs):
        return self.score(pairs)

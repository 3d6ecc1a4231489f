"""The system under test, and the only file here that imports the program.

Builds what ``VectorStoreServer`` builds for its index — a
``SentenceTransformerEmbedder`` and the index that
``UsearchKnn(...)._index_factory()`` returns, with the encoder attached —
and exposes the three calls ``ExternalIndexNode`` makes on it: the first
of ``_embed_fns()`` (``data_embed``), ``add_batch_device``/``remove``, and
``search_batch`` on texts (the fused query program).

Which architecture a model's name stands for is the program's to say
(``SentenceTransformerEmbedder(model["name"])``, the normal path); the
benchmark holds it to the configuration by geometry — sequence length,
row width — and by the seed's leaves matching its parameter tree one for
one, names and shapes, whatever the family.
"""

from __future__ import annotations

import collections

import jax
import numpy as np

from . import reference, weights as weights_mod


class System:
    def __init__(self, config: dict, weights):
        """``weights``: the handle of ``weights.py``."""
        from pathway_tpu.stdlib.indexing.nearest_neighbors import UsearchKnn
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

        model, index = config["model"], config["index"]
        mesh = None
        if config.get("mesh"):
            from pathway_tpu.parallel.mesh import resolve_mesh

            mesh = resolve_mesh(config["mesh"])
        self.embedder = SentenceTransformerEmbedder(model["name"], mesh=mesh)
        encoder = self.embedder._encoder
        if encoder.max_seq_len != model["max_seq_len"] or encoder.dim != index["dimensions"]:
            raise SystemExit("the program's encoder is not the one the configuration describes")
        encoder.params = _lay_over(encoder.params, weights)
        knn = UsearchKnn(
            None,
            dimensions=index["dimensions"],
            reserved_space=index["reserved_space"],
            metric=index["metric"],
            embedder=self.embedder,
            mesh=mesh,
        )
        self.index = knn._index_factory()()
        self.data_embed = knn._embed_fns()[0]
        self.k = int(index["k"])
        self.events = collections.Counter()
        for name in ("_grow", "_upload_full", "_refresh_host"):
            self._count_calls(name)
        self.capacity = self.index.capacity

    def _count_calls(self, name: str) -> None:
        """Count the calls of a method that moves the whole slab; the
        window may make none."""
        inner = getattr(self.index, name)

        def counted(*args, **kwargs):
            self.events[name] += 1
            return inner(*args, **kwargs)

        setattr(self.index, name, counted)

    # -- set-up ---------------------------------------------------------------

    def embed_pool(self, texts):
        return self.embedder.encode_device(texts)

    def fill(self, pool_emb, config: dict, seed: int) -> None:
        """The standing corpus, made on the device from the pool and the
        seed's noise, in through ``add_batch_device`` chunk by chunk."""
        rows, chunk = int(config["rows"]), int(config["fill_chunk"])
        key = weights_mod.seed_key(seed, 11)
        sigma = float(config["standing_noise_sigma"])
        for c in range(-(-rows // chunk)):
            block = reference.standing_chunk(key, c, pool_emb, sigma, chunk=chunk)
            keys = list(range(c * chunk, min((c + 1) * chunk, rows)))
            self.index.add_batch_device(keys, block, None)
        self.block_until_visible()

    # -- the calls of ExternalIndexNode.process -------------------------------

    def remove(self, keys) -> None:
        for key in keys:
            self.index.remove(key)

    def embed_and_add(self, keys, texts) -> None:
        self.index.add_batch_device(keys, self.data_embed(texts), None)

    def block_until_visible(self) -> None:
        jax.block_until_ready((self.index._dev_matrix, self.index._dev_valid))

    def search(self, texts):
        return self.index.search_batch(texts, self.k)

    # -- after the window -----------------------------------------------------

    def slab_moves(self) -> dict:
        moved = dict(self.events)
        if self.index.capacity != self.capacity:
            moved["capacity_changed"] = 1
        return moved

    def free(self) -> None:
        idx = self.index
        idx._dev_matrix = idx._dev_valid = idx._dev_bias = None
        self.embedder._encoder.params = None


def _lay_over(tree, weights):
    """The seed's weights in the shape and the types of the program's
    parameter tree: same leaves, same shapes, or an error. Made a group
    of the handle's at a time, each leaf cast to the type of the one it
    replaces, so that no more float32 than a group is ever beside them."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    shapes = {name: weights.shape(name) for name in weights.names()}
    place = {}
    for at, (path, leaf) in enumerate(paths):
        parts = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        name = "/".join(p for p in parts if p not in (None, "params", "value"))
        if shapes.get(name) != tuple(leaf.shape):
            raise SystemExit(f"the program's parameter {name!r} {leaf.shape} has no match in the seed's weights")
        place[name] = (at, leaf.dtype)
    grouped = [name for group in weights.groups() for name in group]
    if sorted(grouped) != sorted(place):
        raise SystemExit(f"weights the program has no place for: {sorted(set(grouped) ^ set(place))}")
    leaves = [None] * len(paths)
    for group in weights.groups():
        for name, made in weights.take(group).items():
            at, dtype = place[name]
            leaves[at] = made.astype(dtype)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def device_facts(chips: int) -> dict:
    """What JAX reports; a run without the chips its cell asks for ends here."""
    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if facts["platform"] != "tpu" or len(devices) < chips:
        raise SystemExit(f"this cell needs {chips} TPU chip(s); JAX found {facts}")
    facts["count"] = chips
    return facts


def memory_peak_bytes(chips: int) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices()[:chips])


def compile_cache():
    from pathway_tpu.internals.compile_cache import compile_cache_stats, configure_compile_cache

    return configure_compile_cache(), compile_cache_stats


def native_tokenizer_loaded() -> bool:
    from pathway_tpu import native

    return native.is_available()

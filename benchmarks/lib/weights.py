"""The encoder's weights, made on the device from the seed in one jitted
call, in float32 as the program holds them. A flat dict, name -> array;
``system.py`` lays it over the program's parameter tree and
``reference.py`` reads it as it is.

The scales are the configuration's (``weights`` in its file): BERT's
0.02 for every matrix, a larger table of word vectors and smaller
position and type vectors, so that a mean-pooled embedding depends on
the words and not on what every document shares (PERF.md, Findings,
PR 21: seeded flax defaults give cosine 0.9995 between unrelated texts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def weight_shapes(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) for a BERT-style encoder of these sizes."""
    d, inter = model["hidden_size"], model["intermediate_size"]
    shapes = {
        "tok_embed/embedding": ((model["vocab_size"], d), "word"),
        "pos_embed/embedding": ((model["max_position_embeddings"], d), "position"),
        "type_embed/embedding": ((model["type_vocab_size"], d), "type"),
        "ln_embed/scale": ((d,), "one"),
        "ln_embed/bias": ((d,), "zero"),
    }
    for i in range(model["num_hidden_layers"]):
        p = f"layer_{i}/"
        for name, shape in (
            ("attention/qkv", (d, 3 * d)),
            ("attention/out", (d, d)),
            ("mlp_in", (d, inter)),
            ("mlp_out", (inter, d)),
        ):
            shapes[p + name + "/kernel"] = (shape, "matrix")
            shapes[p + name + "/bias"] = ((shape[1],), "zero")
        for ln in ("ln_att", "ln_mlp"):
            shapes[p + ln + "/scale"] = ((d,), "one")
            shapes[p + ln + "/bias"] = ((d,), "zero")
    return shapes


def seed_key(seed: int, stream: int):
    """A PRNG key from a seed of any size (the driver's pass 2**31)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def make_weights(model: dict, scales: dict, seed: int) -> dict:
    shapes = weight_shapes(model)
    names = sorted(shapes)

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = shapes[name]
            if kind == "one":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zero":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                std = scales[kind + "_std"]
                out[name] = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return build(seed_key(seed, 7))

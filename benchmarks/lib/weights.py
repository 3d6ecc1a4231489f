"""The seed's weights as a function of (seed, leaf), not a resident tree.

``make_weights`` returns a handle. What leaves there are, their shapes
and how each is drawn is the configuration's family's to say
(``leaves``, ``make_leaf``: ``spec.py``); the handle gives every leaf a key
of its own, ``fold_in(seed_key(seed, 7), i)`` with ``i`` its rank in the
sorted names, and makes the leaves asked for on the device in one jitted
call, in float32. It keeps no array: ``system.py`` takes the leaves to lay
them over the program's parameter tree, in the program's types, and the
family's reference takes them again once the program's are freed — all at
once where the model is small, a layer at a time where it is not.
"""

from __future__ import annotations

import functools

import jax


def seed_key(seed: int, stream: int):
    """A PRNG key from a seed of any size (the driver's pass 2**31)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


class Weights:
    def __init__(self, family, model: dict, scales: dict, seed: int):
        self._leaves = family.leaves(model)
        self._names = sorted(self._leaves)
        self._rank = {name: i for i, name in enumerate(self._names)}
        self._groups = [list(g) for g in family.take_groups(model)]
        self._seed = seed

        # a compiled program per set of names asked for; no array
        @functools.partial(jax.jit, static_argnums=(1,))
        def build(key, names):
            out = {}
            for name in names:
                shape, kind = self._leaves[name]
                out[name] = family.make_leaf(kind, shape, jax.random.fold_in(key, self._rank[name]), scales)
            return out

        self._build = build

    def names(self) -> list[str]:
        return list(self._names)

    def shape(self, name: str) -> tuple[int, ...]:
        return tuple(self._leaves[name][0])

    def groups(self) -> list[list[str]]:
        """The names in the groups the family wants them made in, each
        small enough to sit on the device beside what is resident."""
        return self._groups

    def take(self, names) -> dict:
        """name -> float32 array on the device, made now and kept nowhere."""
        return self._build(seed_key(self._seed, 7), tuple(names))


def make_weights(family, model: dict, scales: dict, seed: int) -> Weights:
    return Weights(family, model, scales, seed)

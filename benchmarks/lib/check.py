"""The comparison that decides ``correct``: what the window's own calls
answered, against the plain reference, once the window has closed.

A sample of the window's answers, drawn from the seed, is replayed:
the reference index takes the window's write batches in order, and each
sampled answer is judged against the reference's rows as they stood
when that answer was produced (its ``version``: how many write batches
had been acknowledged). That holds the guarantee too: an answer after an
acknowledged write is judged against the new version of every key.

Numbers compared, each with its limit (``correct.limits`` in the
configuration's file; PERF.md gives the readings they were set from):

- ``rank_gap``: the widest gap, over the sample, by which the best key
  the answer left out beats the worst key it returned, in the
  reference's scores. 0 where the answer is the reference's own top-k.
- ``score_err``: the widest gap between a returned score and the
  reference's score of that key at that version.
- exact counts, limit 0: answers that are not ``k`` distinct standing
  keys, requests never finished, calls that moved the whole slab, and
  compilations inside the window.
- ``fresh_checked``: how many sampled answers came after a write of the
  document they ask about was acknowledged; at least ``min_fresh``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import reference, weights as weights_mod

GROUP = 8  # queries judged in one call


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value >= self.limit if self.at_least else self.value <= self.limit

    def line(self) -> str:
        sign = ">=" if self.at_least else "<="
        return f"{self.name} {self.value:.6g} (limit {sign} {self.limit:.6g}){'' if self.ok else '  FAILED'}"


def malformed(answers, k: int, rows: int) -> int:
    bad = 0
    for answer in answers:
        keys = [key for key, _ in answer]
        if (
            len(keys) != k
            or len(set(keys)) != k
            or not all(isinstance(key, (int, np.integer)) and 0 <= key < rows for key in keys)
        ):
            bad += 1
    return bad


def judge(config, traffic, family, weights, seed, queries, first_version: int, control: str | None = None) -> dict:
    """-> {"rank_gap", "score_err", "fresh_checked", "sampled"} for the
    window's answers and, with ``control`` ("fp8" or "int8"), the same
    under "control" for the reference in that precision put in the
    program's place. ``family`` is the configuration's (``spec.py``): its
    ``encode`` is the reference encoder, and ``weights`` the handle it
    takes its leaves from."""
    model, k, rows = config["model"], int(config["index"]["k"]), int(config["rows"])
    rng = np.random.default_rng([seed, 2])
    usable = [q for q in queries if q.answer is not None and len(q.answer) == k]
    n = min(int(config["correct"]["sample_queries"]), len(usable))
    if n == 0:
        return {"rank_gap": float("inf"), "score_err": float("inf"), "fresh_checked": 0, "sampled": 0}
    sample = [usable[i] for i in sorted(rng.choice(len(usable), n, replace=False))]
    sample.sort(key=lambda q: q.version)

    key = weights_mod.seed_key(seed, 11)
    sigma, chunk = float(config["standing_noise_sigma"]), int(config["fill_chunk"])
    sides = {"reference": None, **({"control": control} if control else {})}
    index, q_emb = {}, {}
    for side, quant in sides.items():
        pool_emb = family.encode(weights, model, traffic.pool_texts, quant=quant)
        q_emb[side] = family.encode(weights, model, [q.text for q in sample], quant=quant)
        index[side] = reference.ReferenceIndex(pool_emb, rows, sigma, key, chunk, quant=quant)

    out = {side: {"rank_gap": 0.0, "score_err": 0.0} for side in sides}
    written: dict[int, int] = {}  # pool document -> version at which a key last got its text
    fresh, version, at = 0, 0, 0
    while at < len(sample):
        v = sample[at].version
        while version < v:
            keys, docs = traffic.write_batch(version)
            for idx in index.values():
                idx.replace(keys, docs)
            version += 1
            written.update((int(d), version) for d in docs)
        group = [q for q in sample[at : at + GROUP] if q.version == v]
        rows_of = np.arange(at, at + len(group))
        pad = GROUP - len(group)
        ref_q = np.pad(np.asarray(q_emb["reference"][rows_of]), ((0, pad), (0, 0)))
        answers = {"reference": [q.answer for q in group]}
        if control:
            ctrl_q = np.pad(np.asarray(q_emb["control"][rows_of]), ((0, pad), (0, 0)))
            idx_c, val_c = index["control"].answer(ctrl_q, k)
            answers["control"] = [list(zip(idx_c[i].tolist(), val_c[i].tolist())) for i in range(len(group))]
        for side, ans in answers.items():
            keys = np.zeros((GROUP, k), np.int64)
            said = np.zeros((GROUP, k), np.float64)
            for i, a in enumerate(ans):
                keys[i] = [key_ for key_, _ in a]
                said[i] = [s for _, s in a]
            returned, best_out = index["reference"].judge(ref_q, keys)
            m = len(group)
            gap = np.maximum(0.0, best_out[:m] - returned[:m].min(axis=1))
            err = np.abs(said[:m] - returned[:m]).max(axis=1)
            o = out[side]
            o["rank_gap"] = max(o["rank_gap"], float(gap.max()))
            o["score_err"] = max(o["score_err"], float(err.max()))
        fresh += sum(1 for q in group if first_version < written.get(q.doc, 1 << 60) <= v)
        at += len(group)
    result = dict(out["reference"], fresh_checked=fresh, sampled=n)
    if control:
        result["control"] = out["control"]
    return result


def numbers(config: dict, judged: dict, exact: dict) -> list[Number]:
    limits = config["correct"]["limits"]
    rows = [
        Number("rank_gap", judged["rank_gap"], limits["rank_gap"]),
        Number("score_err", judged["score_err"], limits["score_err"]),
        Number("fresh_checked", judged["fresh_checked"], config["correct"]["min_fresh"], at_least=True),
    ]
    rows += [Number(name, value, 0) for name, value in exact.items()]
    return rows

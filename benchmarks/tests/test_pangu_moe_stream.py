"""The faults of ``test_pangu_moe.py`` planted again where the
latent-attention encoder runs since it packs its write batch
(``apply_stream``: one token stream, told where its documents start, its
attention the visit-form kernel), and the faults only a stream can have:
a query that attends the documents before its own, and queries turned by
their place in the stream instead of their place in their document. Each
comes out not ``correct`` in the rehearsal of
``doc-pangu-moe.backfill-b32``, by ``rank_gap`` or ``score_err`` at the
configuration's own limits.

The preset's attention runs here in the Pallas interpreter, as the
expert layer does: the kernel is what a write batch runs on the chip.
``test_pangu_moe.py`` plants the shared expert's absence in ``_moe``, the
padded forward's, which only the query program still runs; here it is
planted in the product both forwards make. Its rehearsal, its control
and its other faults hold as they are, and its sizes are used here.

Slow for unit tests; the benchmark's own and not part of tier-1.
"""

import functools

import jax.numpy as jnp
import pytest

from benchmarks.tests.test_pangu_moe import (
    PRESET,
    TINY_CFG,
    _dropped_over_capacity,
    _post_attention_norm_skipped,
    _route_with,
    rehearse,
)
from pathway_tpu.models import latent_moe, sentence_encoder
from pathway_tpu.models.latent_moe import LatentMoEConfig
from pathway_tpu.ops import mla_attention


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    preset = functools.partial(
        LatentMoEConfig.tiny_for_tests, expert_impl="interpret", attention_impl="interpret", dtype=jnp.float32
    )
    monkeypatch.setitem(sentence_encoder.ARCHITECTURES, PRESET, preset)


def _documents_merged(original):
    """The kernel told every real token is of one document: a query
    attends every earlier document of its stream."""

    def attend(q_nope, q_rope, k_nope, k_rope, v, seg, *rest, **kw):
        return original(q_nope, q_rope, k_nope, k_rope, v, jnp.where(seg >= 0, 0, seg), *rest, **kw)

    return attend


def _queries_turned_by_stream_position(original):
    """The queries' rope rows taken by their row in the stream, the keys'
    by their place in their document."""

    def attend(q_nope, q_rope, k_nope, k_rope, v, seg, pos, cos, sin, **kw):
        t = pos.shape[0]
        cos, sin = latent_moe._rope_table(t, TINY_CFG.qk_rope_head_dim, TINY_CFG.rope_theta)
        return original(q_nope, q_rope, k_nope, k_rope, v, seg, jnp.arange(t, dtype=pos.dtype), cos, sin, **kw)

    return attend


def _top_k_minus_one(original):
    """One choice fewer a token, its place kept with no weight."""
    fewer = _route_with(original, top_k=TINY_CFG.num_experts_per_tok - 1)

    def route(scores, top_k, **kw):
        ids, weights = fewer(scores, top_k, **kw)
        return jnp.pad(ids, ((0, 0), (0, 1))), jnp.pad(weights, ((0, 0), (0, 1)))

    return route


def _shared_expert_left_out(original):
    """The shared expert's product adds nothing; the dense feed-forward's runs."""
    shared = TINY_CFG.n_shared_experts * TINY_CFG.moe_intermediate_size

    def swiglu(p, h):
        out = original(p, h)
        return out * 0.0 if p["gate"]["kernel"].shape[1] == shared else out

    return swiglu


FAULTS = [
    "documents_merged", "queries_turned_by_stream_position", "weights_not_normalised", "top_k_minus_one",
    "post_norm_skipped", "rope_dropped", "shared_expert_left_out", "dropped_over_capacity",
]  # fmt: skip


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_in_the_stream_forward_is_not_correct(monkeypatch, fault):
    if fault == "documents_merged":
        monkeypatch.setattr(mla_attention, "mla_attention", _documents_merged(mla_attention.mla_attention))
    elif fault == "queries_turned_by_stream_position":
        monkeypatch.setattr(mla_attention, "mla_attention", _queries_turned_by_stream_position(mla_attention.mla_attention))
    elif fault == "weights_not_normalised":
        monkeypatch.setattr(latent_moe, "route", _route_with(latent_moe.route, norm_topk=False))
    elif fault == "top_k_minus_one":
        monkeypatch.setattr(latent_moe, "route", _top_k_minus_one(latent_moe.route))
    elif fault == "post_norm_skipped":
        monkeypatch.setattr(latent_moe, "_rmsnorm", _post_attention_norm_skipped(latent_moe._rmsnorm))
    elif fault == "rope_dropped":
        monkeypatch.setattr(latent_moe, "_rope", lambda x, cos, sin: x)
    elif fault == "shared_expert_left_out":
        monkeypatch.setattr(latent_moe, "_swiglu", _shared_expert_left_out(latent_moe._swiglu))
    else:
        monkeypatch.setattr(latent_moe, "held_expert_sum", _dropped_over_capacity(latent_moe.held_expert_sum))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]


def test_the_sound_stream_is_correct():
    """The control of the faults above: the same rehearsal, nothing planted."""
    result = rehearse()
    assert result["correct"], result["check"]

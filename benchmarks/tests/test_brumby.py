"""The cell ``doc-brumby.backfill-long-b8`` rehearsed on the CPU at tiny
sizes: ``correct`` comes out true for the program as it is, and false for
its fp8 control and for each fault planted in the timed path of the
power-retention encoder, at the configuration's own weight scales and
limits; the family's work functions and the cell's own per-layer metrics
by hand.

``System`` hands the program only ``model["name"]``, and 3.4 B parameters
do not rehearse on a CPU: the overrides name the one tiny preset the
program's table carries for tests, set the model's size keys to it and
shorten the documents to its 256 positions (eight strata still, so a
write batch is still eight documents of unequal length in one stream).
The preset's retention runs in the Pallas interpreter, which the test
asks for by patching the table's entry (no production entry infers it) —
and in float32: the limits are set from rows of 5,120 on the chip, and a
row of width 64 averages the rounding of bfloat16 matmul inputs over 80
times fewer dims (it reads rank_gap 1.2e-3 and score_err 2.2e-3 here).

Slow for unit tests; the benchmark's own and not part of tier-1.
"""

import functools

import jax.numpy as jnp
import pytest

from benchmarks.lib import runner, spec
from pathway_tpu.models import power_retention as model_mod, sentence_encoder
from pathway_tpu.models.power_retention import PowerRetentionConfig
from pathway_tpu.ops.power_retention import segment_cumsum

CELL = "doc-brumby.backfill-long-b8"
PRESET = "power-retention-tiny-for-tests"
TINY_CFG = PowerRetentionConfig.tiny_for_tests()
SIZE_KEYS = (
    "head_dim", "hidden_size", "intermediate_size", "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
    "vocab_size", "gate_bias", "max_seq_len",
)  # fmt: skip
TINY = {
    "rows": 4000,  # a write batch is 8 keys and a CPU turn without the interpreter a few ms
    "pool_docs": 64,
    "index.reserved_space": 4096,
    "index.dimensions": TINY_CFG.hidden_size,
    "fill_chunk": 1024,
    "correct.sample_queries": 64,
    "correct.min_fresh": 1,
    "documents.median_words": 40,
    "documents.min_words": 4,
    "documents.max_words": 254,
    "model.name": PRESET,
    **{"model." + key: getattr(TINY_CFG, key) for key in SIZE_KEYS},
}
SECONDS = 4.0


def tiny(**kw):
    return functools.partial(PowerRetentionConfig.tiny_for_tests, retention_impl="interpret", dtype=jnp.float32, **kw)


@pytest.fixture(autouse=True)
def interpreted_retention(monkeypatch):
    monkeypatch.setitem(sentence_encoder.ARCHITECTURES, PRESET, tiny())


def rehearse(seed=5, control=None):
    return runner.run_cell(spec.load_cell(CELL), seed, SECONDS, False, control=control, rehearsal=TINY)


def test_sound_run_is_correct_and_its_control_is_not():
    result = rehearse(control="fp8")
    assert result["correct"], result["check"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"  # never a device metric
    assert result["check"]["fresh_checked"][0] >= 1
    assert result["control"]["fails"], result["control"]


# ---- faults, each under the timed path ---------------------------------------------


def _pairs(q, k, v, log_g, seg, pos, *, degree, eps, gate=True, normalise=True, strict=False, leak=False, **_):
    """The retention of a stream as whole ``[t, t]`` weights, with a
    fault switched on: the gate dropped, the normaliser dropped, a token
    not seeing itself, or documents seeing their predecessors in the stream."""
    t, kv = log_g.shape
    dim = k.shape[1] // kv
    q = q.astype(jnp.float32).reshape(t, kv, -1, dim)
    k, v = k.astype(jnp.float32).reshape(t, kv, dim), v.astype(jnp.float32).reshape(t, kv, dim)
    first = jnp.arange(t) == 0 if leak else pos == 0
    total = segment_cumsum(log_g if gate else jnp.zeros_like(log_g), first)
    at = jnp.arange(t)
    keep = (at[None, :] < at[:, None]) if strict else (at[None, :] <= at[:, None])
    keep &= (seg[None, :] >= 0) if leak else (seg[:, None] == seg[None, :])
    decay = jnp.exp(jnp.where(keep[None], total.T[:, :, None] - total.T[:, None, :], -1e30))
    weights = decay[:, None] * jnp.einsum("ibgd,jbd->bgij", q, k, precision="highest") ** degree
    num = jnp.einsum("bgij,jbd->ibgd", weights, v, precision="highest")
    if normalise:
        num = num / (jnp.moveaxis(weights.sum(-1), 2, 0)[..., None] + eps)
    return num.reshape(t, -1).astype(k.dtype)


def _head_norms_skipped(original):
    """Of a layer's norms the two over a head's dims (``[tokens, heads,
    head_dim]``) only scale."""

    def rmsnorm(x, scale, eps):
        return x.astype(jnp.float32) * scale if x.ndim == 3 else original(x, scale, eps)

    return rmsnorm


FAULTS = {
    "degree_one": None,
    "gate_dropped": dict(gate=False),
    "normaliser_dropped": dict(normalise=False),
    "rope_dropped": None,
    "head_norms_skipped": None,
    "causal_mask_off_by_one": dict(strict=True),
    "leak_across_a_document_boundary": dict(leak=True),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_encoder_is_not_correct(monkeypatch, fault):
    if fault == "degree_one":
        monkeypatch.setitem(sentence_encoder.ARCHITECTURES, PRESET, tiny(degree=1))
    elif fault == "rope_dropped":
        monkeypatch.setattr(model_mod, "_rope", lambda x, cos, sin: x)
    elif fault == "head_norms_skipped":
        monkeypatch.setattr(model_mod, "_rmsnorm", _head_norms_skipped(model_mod._rmsnorm))
    else:
        monkeypatch.setattr(model_mod, "power_retention", functools.partial(_pairs, **FAULTS[fault]))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]


def test_the_pair_form_without_a_fault_is_correct(monkeypatch):
    """What the faults are planted in is itself sound."""
    monkeypatch.setattr(model_mod, "power_retention", _pairs)
    result = rehearse()
    assert result["correct"], result["check"]


# ---- the family's work, and the per-layer metrics this configuration brings -----------


def published():
    return spec.load_cell(CELL).config["model"]


def test_family_work_by_hand():
    family, model = spec.load_family("brumby"), published()
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408  # the matmul parameters of a layer
    assert layer == 330_342_400  # the layer's 330,352,896 less its four norms' 10,496
    pair = 40 * 4 * 128
    assert family.flops(model, [1]) == 8 * (2 * layer + pair)
    assert family.flops(model, [3, 5]) == 8 * (8 * 2 * layer + (6 + 15) * pair)
    assert family.layer_call_flops(model, 8, 21) * 8 == family.flops(model, [3, 5])
    assert family.retention_flops(model, 21) == 21 * pair
    assert family.retention_bytes(model, 3) == 3 * 2 * (40 + 8) * 128 * 2
    # a token through the state: the update of 8 heads and the read of 40, against pairs of 20,480
    features = 128 * 129 // 2
    through = 8 * 2 * features * 128 + 40 * 2 * features * 129
    assert family.state_crossover(model) == pytest.approx(2 * through / pair - 1)
    assert 9_000 < family.state_crossover(model) < 10_500 and model["max_seq_len"] < family.state_crossover(model)
    with pytest.raises(SystemExit):
        family.leaves(dict(model, max_seq_len=16_384))
    assert sum(int(jnp.prod(jnp.array(shape))) for shape, _ in family.leaves(model).values()) == 3_420_740_608
    assert [len(g) for g in family.take_groups(model)] == [2] + [12] * 8


def test_retention_metrics_by_hand_and_silent_without_their_stage(monkeypatch):
    """Ten write batches in a traced window: 80 layer calls over 7,856
    real tokens and 11.9 M causal pairs each, 9,216 token rows computed."""
    import pathway_tpu.tracing

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.layer_metrics}
    ctx = {
        "trace": {
            "op_s": {
                "%power_retention = bf16[16384,5120] custom-call": 0.25,
                "%power_retention = bf16[128,5120] custom-call": 0.01,  # the query program's: not counted
                "%fusion = f32[1024,17408] fusion": 5.0,
            },
            "module_s": {"jit_apply_stream": 6.5, "jit_fused": 0.3},
        },
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    calls, tokens, pairs, computed = 80, 80 * 7_856, 80 * 11_900_000, 80 * 9_216
    totals = {"embed_retention": {"calls": calls, "seconds": 0.0, "tokens": tokens, "rows": pairs, "computed_tokens": computed}}
    monkeypatch.setattr(pathway_tpu.tracing, "stage_totals", lambda: totals, raising=False)
    pair, layer = 40 * 4 * 128, 330_342_400
    least = pairs * pair / 197e12
    assert least > calls * 2 * 48 * 128 * 2 / 819e9  # the pairs' FLOPs, not the bytes
    assert by_name["retention_kernel_roofline_pct"].read(ctx) == pytest.approx(100 * least / 0.25)
    work = tokens * 2 * layer + pairs * pair
    assert by_name["retention_encode_roofline_pct"].read(ctx) == pytest.approx(100 * work / 197e12 / 6.5)
    assert by_name["embed_computed_over_real_tokens"].read(ctx) == pytest.approx(9_216 / 7_856)
    monkeypatch.setattr(pathway_tpu.tracing, "stage_totals", lambda: {}, raising=False)  # a program from before the stage
    for name in ("retention_kernel_roofline_pct", "retention_encode_roofline_pct", "embed_computed_over_real_tokens"):
        assert by_name[name].read(ctx) is None

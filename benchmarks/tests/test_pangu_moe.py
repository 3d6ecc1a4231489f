"""The cell ``doc-pangu-moe.backfill-b32`` rehearsed on the CPU at tiny
sizes: ``correct`` comes out true for the program as it is, and false for
its fp8 control and for each fault planted in the timed path of the
latent-attention / sparse-expert encoder, at the configuration's own
weight scales and limits.

``System`` hands the program only ``model["name"]``, and 4.8 B parameters
do not rehearse on a CPU: the overrides name the one tiny preset the
program's table carries for tests and set the model's size keys to it
(all 8 of its experts held: the share is a value of the configuration,
``tests/test_latent_moe.py`` adds the shares up). The preset's grouped
product runs in the Pallas interpreter, which the test asks for by
patching the table's entry (no production entry infers it) — and in
float32: with 8 experts, top-2 and all of them held, a token whose second
and third scores swap under bfloat16 rounding changes half of its routed
weight (at the published size an eighth of it, and only where the expert
is one of the 16 of 256 held), which a row of width 64 pooled over some
tens of tokens does not average away.

Slow for unit tests; the benchmark's own and not part of tier-1.
"""

import functools

import jax.numpy as jnp
import pytest

from benchmarks.lib import runner, spec
from pathway_tpu.models import latent_moe, sentence_encoder
from pathway_tpu.models.latent_moe import LatentMoEConfig, LatentMoEEncoder

CELL = "doc-pangu-moe.backfill-b32"
PRESET = "latent-moe-tiny-for-tests"
TINY_CFG = LatentMoEConfig.tiny_for_tests()
SIZE_KEYS = (
    "first_k_dense_replace", "hidden_size", "intermediate_size", "kv_lora_rank", "moe_intermediate_size",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
)  # fmt: skip
TINY = {
    "rows": 1000,
    "pool_docs": 64,
    "index.reserved_space": 1024,
    "index.dimensions": TINY_CFG.hidden_size,
    "fill_chunk": 256,
    "correct.sample_queries": 64,
    "correct.min_fresh": 1,
    "model.name": PRESET,
    "model.router_experts": TINY_CFG.n_routed_experts,
    "model.n_routed_experts": TINY_CFG.experts_held[1],
    "model.experts_first": TINY_CFG.experts_held[0],
    **{"model." + key: getattr(TINY_CFG, key) for key in SIZE_KEYS},
}
SECONDS = 4.0


@pytest.fixture(autouse=True)
def interpreted_experts(monkeypatch):
    monkeypatch.setitem(
        sentence_encoder.ARCHITECTURES, PRESET, functools.partial(LatentMoEConfig.tiny_for_tests, expert_impl="interpret", dtype=jnp.float32)
    )


def rehearse(seed=5, control=None):
    return runner.run_cell(spec.load_cell(CELL), seed, SECONDS, False, control=control, rehearsal=TINY)


def test_sound_run_is_correct_and_its_control_is_not():
    result = rehearse(control="fp8")
    assert result["correct"], result["check"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"  # never a device metric
    assert result["check"]["fresh_checked"][0] >= 1
    assert result["control"]["fails"], result["control"]


# ---- faults, each under the timed path ---------------------------------------------


def _route_with(original, **forced):
    def route(scores, top_k, **kw):
        top_k = forced.get("top_k", top_k)
        return original(scores, top_k, **{**kw, **{k: v for k, v in forced.items() if k != "top_k"}})

    return route


def _post_attention_norm_skipped(original):
    """Of a layer's six norms (in, q_a, kv_a, post_attn, pre_mlp,
    post_mlp) the fourth only scales."""
    calls = [0]
    per_forward = 6 * TINY_CFG.num_hidden_layers + 1

    def rmsnorm(x, scale, eps):
        at = calls[0] % per_forward
        calls[0] += 1
        if at < per_forward - 1 and at % 6 == 3:
            return x.astype(jnp.float32) * scale
        return original(x, scale, eps)

    return rmsnorm


def _shared_expert_left_out(original):
    def moe(self, p, h, mask):
        out, loads = original(self, p, h, mask)
        return out - latent_moe._swiglu(p["shared"], h), loads

    return moe


def _dropped_over_capacity(original, factor=1.0):
    """The classic capacity limit: an expert takes the first
    ``factor * mean load`` of its tokens, in token order, and the rest
    are dropped."""

    def held_expert_sum(x, expert_ids, weights, real, *ws, first, experts, **kw):
        tokens, top_k = expert_ids.shape
        most = int(factor * tokens * top_k / experts)
        flat = expert_ids.reshape(-1)
        taken = jnp.cumsum(flat[:, None] == jnp.arange(experts)[None, :], axis=0)
        place = jnp.take_along_axis(taken, flat[:, None], axis=1)[:, 0]
        kept = jnp.where(place <= most, flat, -1).reshape(tokens, top_k)
        return original(x, kept, weights, real, *ws, first=first, experts=experts, **kw)

    return held_expert_sum


FAULTS = ["weights_not_normalised", "top_k_minus_one", "post_norm_skipped", "rope_dropped", "shared_expert_left_out", "dropped_over_capacity"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_in_the_encoder_is_not_correct(monkeypatch, fault):
    if fault == "weights_not_normalised":
        monkeypatch.setattr(latent_moe, "route", _route_with(latent_moe.route, norm_topk=False))
    elif fault == "top_k_minus_one":
        monkeypatch.setattr(latent_moe, "route", _route_with(latent_moe.route, top_k=TINY_CFG.num_experts_per_tok - 1))
    elif fault == "post_norm_skipped":
        monkeypatch.setattr(latent_moe, "_rmsnorm", _post_attention_norm_skipped(latent_moe._rmsnorm))
    elif fault == "rope_dropped":
        monkeypatch.setattr(latent_moe, "_rope", lambda x, cos, sin: x)
    elif fault == "shared_expert_left_out":
        monkeypatch.setattr(LatentMoEEncoder, "_moe", _shared_expert_left_out(LatentMoEEncoder._moe))
    else:
        monkeypatch.setattr(latent_moe, "held_expert_sum", _dropped_over_capacity(latent_moe.held_expert_sum))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]


# ---- the per-layer metrics this configuration brings ---------------------------------


def test_expert_roofline_by_hand_and_silent_without_its_stage(monkeypatch):
    """213 runs of the gate and up products in 0.2346 s and as many of
    the down product, 106 layer calls of ~2,200 assignments: bytes-bound."""
    import pathway_tpu.tracing

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.layer_metrics}
    ctx = {
        "trace": {
            "op_s": {
                "%expert_grouped_matmul = f32[4096,2048] custom-call": 0.2346,
                "%expert_grouped_matmul = f32[4096,7680] custom-call": 0.1402,
                "%expert_grouped_matmul = f32[128,2048] custom-call": 0.05,  # the query program's: not counted
                "%fusion = f32[4096,7680] fusion": 9.0,
            }
        },
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    totals = {"embed_experts": {"calls": 106, "seconds": 0.0, "rows": 233_200, "max_load": 25_000, "mean_load": 14_575.0}}
    monkeypatch.setattr(pathway_tpu.tracing, "stage_totals", lambda: totals, raising=False)
    expert = 3 * 7680 * 2048
    least = max(233_200 * 2 * expert / 197e12, 106 * 16 * expert * 2 / 819e9)
    assert least == 106 * 16 * expert * 2 / 819e9  # the weights' bytes, not the assignments' FLOPs
    assert by_name["moe_expert_roofline_pct"].read(ctx) == pytest.approx(100 * least / (0.2346 + 0.1402))
    assert by_name["moe_expert_load_max_over_mean"].read(ctx) == pytest.approx(25_000 / 14_575)
    monkeypatch.setattr(pathway_tpu.tracing, "stage_totals", lambda: {}, raising=False)  # a program from before the stage
    assert by_name["moe_expert_roofline_pct"].read(ctx) is None
    assert by_name["moe_expert_load_max_over_mean"].read(ctx) is None

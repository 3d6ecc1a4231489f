"""The cell ``doc-jamba2.backfill-b32`` rehearsed on the CPU at tiny sizes:
``correct`` comes out true for the program as it is, and false for its
fp8 control and for each fault planted in the timed path of the hybrid
encoder, at the configuration's own weight scales and limits.

``System`` hands the program only ``model["name"]``, and 3 B parameters
do not rehearse on a CPU: the overrides name the one tiny preset the
program's table carries for tests and set the model's size keys to it.
The preset's scan runs in the Pallas interpreter, which the test asks for
by patching the table's entry (no production entry infers it).

Slow for unit tests; the benchmark's own and not part of tier-1.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import runner, spec
from pathway_tpu.models import hybrid_ssm, sentence_encoder
from pathway_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMEncoder

CELL = "doc-jamba2.backfill-b32"
PRESET = "hybrid-ssm-tiny-for-tests"
TINY_CFG = HybridSSMConfig.tiny_for_tests()
TINY = {
    "rows": 1000,
    "pool_docs": 64,
    "index.reserved_space": 1024,
    "index.dimensions": TINY_CFG.hidden_size,
    "fill_chunk": 256,
    "correct.sample_queries": 64,
    "correct.min_fresh": 1,
    "model.name": PRESET,
    **{
        "model." + key: getattr(TINY_CFG, key)
        for key in ("attn_layer_offset", "attn_layer_period", "hidden_size", "intermediate_size", "mamba_dt_rank", "num_attention_heads", "num_hidden_layers", "vocab_size")
    },
}
SECONDS = 4.0


@pytest.fixture(autouse=True)
def interpreted_scan(monkeypatch):
    monkeypatch.setitem(
        sentence_encoder.ARCHITECTURES, PRESET, functools.partial(HybridSSMConfig.tiny_for_tests, scan_impl="interpret")
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


def _scan_without_carry(u, dt, z, b, c, a, d_skip, *, interpret=False):
    """s_t = (D_t u_t) (x) B_t: the state forgets its predecessor."""
    u32, z32 = u.astype(jnp.float32), z.astype(jnp.float32)
    y = dt * u32 * jnp.sum(b * c, axis=-1, keepdims=True) + d_skip * u32
    return (y * jax.nn.silu(z32)).astype(u.dtype)


def _attention_without_causal_mask(self, p, h, mask):
    c = self.cfg
    b, s, _ = h.shape
    heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = hybrid_ssm._matmul(h, p["q"]["kernel"]).reshape(b, s, kv, heads // kv, hd)
    k = hybrid_ssm._matmul(h, p["k"]["kernel"]).reshape(b, s, kv, hd)
    v = hybrid_ssm._matmul(h, p["v"]["kernel"]).reshape(b, s, kv, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(mask[:, None, None, None, :], scores, -1e30), axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return hybrid_ssm._matmul(ctx.reshape(b, s, heads * hd), p["o"]["kernel"])


def _inner_norms_skipped(original):
    def rmsnorm(x, scale, eps):
        if x.shape[-1] != TINY_CFG.hidden_size:  # dt, B, C
            return x.astype(jnp.float32) * scale
        return original(x, scale, eps)

    return rmsnorm


def _first_mamba_layer_skipped(original):
    calls = [0]
    per_forward = sum(not TINY_CFG.is_attention(i) for i in range(TINY_CFG.num_hidden_layers))

    def mamba(self, p, h):
        calls[0] += 1
        if calls[0] % per_forward == 1:
            return jnp.zeros(h.shape, jnp.float32)
        return original(self, p, h)

    return mamba


@pytest.mark.parametrize("fault", ["scan_without_carry", "attention_not_causal", "inner_norms_skipped", "mamba_layer_skipped"])
def test_fault_in_the_encoder_is_not_correct(monkeypatch, fault):
    if fault == "scan_without_carry":
        monkeypatch.setattr(hybrid_ssm, "selective_scan", _scan_without_carry)
    elif fault == "attention_not_causal":
        monkeypatch.setattr(HybridSSMEncoder, "_attention", _attention_without_causal_mask)
    elif fault == "inner_norms_skipped":
        monkeypatch.setattr(hybrid_ssm, "_rmsnorm", _inner_norms_skipped(hybrid_ssm._rmsnorm))
    else:
        monkeypatch.setattr(HybridSSMEncoder, "_mamba", _first_mamba_layer_skipped(HybridSSMEncoder._mamba))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]

"""The faults of ``test_jamba.py`` planted again where the hybrid encoder
runs since it packs its write batch (``apply_stream``: one token stream,
told where its documents start), and the faults only a stream can have: a
document boundary that is no restart of the scan's state, or of the
convolution's window. Each comes out not ``correct`` in the rehearsal of
``doc-jamba2.backfill-b32``, by ``rank_gap`` or ``score_err`` at the
configuration's own limits.

``test_jamba.py`` patches ``hybrid_ssm.selective_scan``,
``HybridSSMEncoder._attention`` and ``._mamba`` at the signatures of the
padded forward, which is gone; three of its faults raise ``TypeError``
where these fail the comparison. Its rehearsal, its control and its fourth
fault hold as they are, and its fixture and its sizes are used here.

Slow for unit tests; the benchmark's own and not part of tier-1.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from benchmarks.tests.test_jamba import TINY_CFG, interpreted_scan, rehearse  # noqa: F401  (the fixture is autouse here too)
from pathway_tpu.models import hybrid_ssm, token_stream
from pathway_tpu.models.hybrid_ssm import HybridSSMEncoder


def _scan_without_carry(u, dt, z, b, c, a, d_skip, starts, *, live=None, interpret=False):
    """s_t = (D_t u_t) (x) B_t: the state forgets its predecessor."""
    u32, z32 = u.astype(jnp.float32), z.astype(jnp.float32)
    y = dt * u32 * jnp.sum(b * c, axis=-1, keepdims=True) + d_skip * u32
    return (y * jax.nn.silu(z32)).astype(u.dtype)


def _scan_without_restart(original):
    """The state runs on from one document into the next."""

    def scan(u, dt, z, b, c, a, d_skip, starts, **kw):
        return original(u, dt, z, b, c, a, d_skip, starts[:1], **kw)

    return scan


def _conv_without_restart(original):
    """Every tap of the convolution reads, wherever in its document the
    token is: a document's first tokens see the one before it."""

    def layout(starts, lens, t):
        seg, pos, live = original(starts, lens, t)
        return seg, pos + TINY_CFG.mamba_d_conv - 1, live

    return layout


def _attention_without_causal_mask(self, p, x, st, kv):
    """The whole stream at once, masked by document and not by order."""
    c = self.cfg
    heads, groups, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    a = p["attn"]
    h = hybrid_ssm._rmsnorm(x, p["norm_in"]["scale"], c.rms_norm_eps)
    q = hybrid_ssm._matmul(h, a["q"]["kernel"]).reshape(st.t, groups, heads // groups, hd)
    k = hybrid_ssm._matmul(h, a["k"]["kernel"]).reshape(st.t, groups, hd)
    v = jnp.where(st.seg[:, None] >= 0, hybrid_ssm._matmul(h, a["v"]["kernel"]), 0.0).reshape(st.t, groups, hd)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(hd)
    keep = (st.seg[:, None] == st.seg[None, :]) & (st.seg[:, None] >= 0)
    probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -1e30), axis=-1)
    ctx = jnp.einsum("kgqs,skd->qkgd", probs, v)
    return self._mlp(p, x + hybrid_ssm._matmul(ctx.reshape(st.t, heads * hd), a["o"]["kernel"])), kv


def _first_mamba_layer_skipped(original):
    """The first Mamba layer's mixer adds nothing; its feed-forward runs."""
    calls = [0]
    per_forward = sum(not TINY_CFG.is_attention(i) for i in range(TINY_CFG.num_hidden_layers))

    def mamba(self, p, x, st, mixer):
        calls[0] += 1
        if calls[0] % per_forward == 1:
            return self._mlp(p, x), mixer
        return original(self, p, x, st, mixer)

    return mamba


FAULTS = ["scan_without_carry", "scan_without_restart", "conv_without_restart", "attention_not_causal", "mamba_layer_skipped"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_in_the_stream_forward_is_not_correct(monkeypatch, fault):
    if fault == "scan_without_carry":
        monkeypatch.setattr(hybrid_ssm, "selective_scan", _scan_without_carry)
    elif fault == "scan_without_restart":
        monkeypatch.setattr(hybrid_ssm, "selective_scan", _scan_without_restart(hybrid_ssm.selective_scan))
    elif fault == "conv_without_restart":
        monkeypatch.setattr(token_stream, "token_layout", _conv_without_restart(token_stream.token_layout))
    elif fault == "attention_not_causal":
        monkeypatch.setattr(HybridSSMEncoder, "_attention", _attention_without_causal_mask)
    else:
        monkeypatch.setattr(HybridSSMEncoder, "_mamba", _first_mamba_layer_skipped(HybridSSMEncoder._mamba))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]

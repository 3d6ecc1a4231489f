"""Operations and bytes the work needs, from its shapes. Multiply-add = 2.

Copied in arithmetic from ``bench.py:_encoder_flops_per_token`` /
``ops/fused_layer.py:encoder_flops_per_token`` (see PERF.md, Open
questions: the originals are a later PR's to delete); the index scan's
count is new. Counts are of the work, whatever implements it: real
tokens, not the padding a batch shape adds, and live rows, not the
slab's capacity.
"""

from __future__ import annotations

import json
import os


def encoder_flops_per_token(model: dict, seq: int) -> float:
    """Forward FLOPs of one token of a BERT-style encoder among ``seq``
    tokens: qkv, scores and probs@V, output projection, FFN in and out."""
    d, inter, layers = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    per_layer = 2 * d * 3 * d + 2 * 2 * seq * d + 2 * d * d + 2 * 2 * d * inter
    return float(layers * per_layer)


def encoder_flops(model: dict, token_lengths) -> float:
    """Forward FLOPs of encoding texts of these token lengths, each
    attending over its own length."""
    return float(sum(int(n) * encoder_flops_per_token(model, int(n)) for n in token_lengths))


def scan_bytes(rows: int, dim: int, row_bytes_per_value: int) -> float:
    """Bytes one brute-force dispatch has to read: every live row once."""
    return float(rows) * dim * row_bytes_per_value


def scan_flops(queries: int, rows: int, dim: int) -> float:
    """FLOPs of scoring ``queries`` against ``rows``."""
    return 2.0 * queries * rows * dim


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r}; the table has {sorted(table)}")
    return table[device_kind]

"""Operations and bytes the index's work needs, from its shapes, and the
table of peaks. Multiply-add = 2. The model's own FLOPs are its family's
(``families/<name>.py``, ``flops``). Counts are of the work, whatever
implements it: live rows, not the slab's capacity.
"""

from __future__ import annotations

import json
import os


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

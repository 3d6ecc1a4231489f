"""From a profiler trace to numbers: device busy time, time by device
operation and by compiled program, and the idle gaps by what the host
was doing. Works on a neutral list of events, so that the recorded trace
under ``tests/data`` checks the same code the chip run uses.

An event is ``{"plane", "line", "name", "start_ns", "dur_ns"}``. Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per executed HLO operation and ``XLA Modules`` one per program run. The
benchmark's own spans are ``TraceAnnotation``s named ``bench.<span>`` on
a host thread's line, on the same clock.
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced_window"


def load_events(trace_dir: str) -> list[dict]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir`` that the
    reduction reads: device lines and the benchmark's own spans."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {len(paths)}")
    events = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    events.append(
                        {
                            "plane": plane.name,
                            "line": line.name,
                            "name": e.name,
                            "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns),
                        }
                    )
    return events


def load_recorded(path: str) -> list[dict]:
    """A trace kept as a test's data: tables of names and rows of
    ``[plane, line, name, start_ns, dur_ns]``."""
    import json

    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    return [
        {"plane": d["planes"][p], "line": d["lines"][l], "name": d["names"][n], "start_ns": float(a), "dur_ns": float(u)}
        for p, l, n, a, u in d["events"]
    ]


def short_name(hlo: str) -> str:
    """A device operation's name without what changes from build to
    build: ``%fwd_group.9 = bf16[8192,384]{1,0:T(8,128)} custom-call(...)``
    becomes ``%fwd_group = bf16[8192,384] custom-call``; a program's,
    ``jit_fused(9494350518324623239)``, becomes ``jit_fused``."""
    if " = " not in hlo:
        return hlo.split("(", 1)[0]
    left, right = hlo.split(" = ", 1)
    depth, cut = 0, len(right)
    for i, ch in enumerate(right):  # the operands' "(": the first outside the type's braces, after the opcode
        if ch in "{[":
            depth += 1
        elif ch in "}]":
            depth -= 1
        elif ch == "(" and depth == 0 and i > 0 and right[i - 1] != " ":
            cut = i
            break
    head = re.sub(r"\{[^{}]*\}", "", left + " = " + right[:cut])
    return re.sub(r"\.\d+(?= |$)", "", head).strip()[:160]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events, lo: float, hi: float):
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            yield e, a, b


def reduce(events: list[dict], chips: int = 1) -> dict:
    """-> window_s, busy_s (mean over chips), op_s and module_s (name ->
    seconds, summed over chips), device_ops and idle_gaps (top 10 each)."""
    window = [e for e in events if e["name"] == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"the trace has {len(window)} {WINDOW_SPAN} spans, not one")
    lo, hi = window[0]["start_ns"], window[0]["start_ns"] + window[0]["dur_ns"]
    planes = sorted({e["plane"] for e in events if e["plane"].startswith(DEVICE_PREFIX)})[:chips]
    spans = [e for e in events if e["name"].startswith(SPAN_PREFIX) and e["name"] != WINDOW_SPAN]
    op_s: collections.Counter = collections.Counter()
    module_s: collections.Counter = collections.Counter()
    module_runs: collections.Counter = collections.Counter()
    gap_s: collections.Counter = collections.Counter()
    busy_total = 0.0
    for plane in planes:
        ops = [e for e in events if e["plane"] == plane and e["line"] == OPS_LINE]
        busy = _union([(a, b) for _, a, b in _clip(ops, lo, hi)])
        busy_total += sum(b - a for a, b in busy)
        for e, a, b in _clip(ops, lo, hi):
            op_s[short_name(e["name"])] += (b - a) / 1e9
        for e, a, b in _clip((e for e in events if e["plane"] == plane and e["line"] == MODULES_LINE), lo, hi):
            module_s[short_name(e["name"])] += (b - a) / 1e9
            module_runs[short_name(e["name"])] += 1
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            # the innermost of the benchmark's spans that covers most of the gap
            cover: collections.Counter = collections.Counter()
            for s, sa, sb in _clip(spans, a, b):
                cover[s["name"]] += sb - sa
            name = max(cover, key=lambda n: (cover[n], -len(n)))[len(SPAN_PREFIX) :] if cover else "outside_spans"
            gap_s[name] += (b - a) / 1e9
    n = max(1, len(planes))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / 1e9 / n,
        "op_s": dict(op_s),
        "module_s": dict(module_s),
        "module_runs": dict(module_runs),
        "device_ops": [[k, v] for k, v in op_s.most_common(10)],
        "idle_gaps": [[k, v] for k, v in gap_s.most_common(10)],
    }

"""Ratio of two sums over the program's own stage totals
(``pathway_tpu.tracing.stage_totals()``: calls, seconds, rows, queries
and tokens of every ``tracing.span`` stage) and the traced window's
device seconds by compiled program. The program's tracing is on only
while the profiler session is, so the totals cover the traced part of
the window and nothing of set-up, warm-up or the drain.

params: ``numerator`` and ``denominator``, lists of terms
``[stage, field]``, ``["-", stage, field]`` (subtracted) or
``["module_s", regex]`` (device seconds of the programs whose name
matches), and ``scale``. ``None`` where the program has no stage totals
to read or the denominator is not above 0.
"""

import re


def _sum(terms, totals, trace):
    total = 0.0
    for term in terms:
        sign = 1.0
        if term[0] == "-":
            sign, term = -1.0, term[1:]
        if term[0] == "module_s":
            total += sign * sum(s for name, s in trace["module_s"].items() if re.search(term[1], name))
        else:
            stage, field = term
            total += sign * totals.get(stage, {}).get(field, 0.0)
    return total


def read(ctx, params):
    try:
        from pathway_tpu.tracing import stage_totals
    except ImportError:  # a program from before the stages
        return None
    totals = stage_totals()
    denominator = _sum(params["denominator"], totals, ctx["trace"])
    if denominator <= 0:
        return None
    return params.get("scale", 1.0) * _sum(params["numerator"], totals, ctx["trace"]) / denominator

"""Roofline share of a compiled program or of one of its operations: the
least time the chip could take for the work of its runs in the traced
window, over the device time those runs took.

params: ``line`` (``module_s`` for whole programs, ``op_s`` for single
operations), ``match`` (a regular expression on the name), ``runs_of``
(a regular expression on the program names whose runs are counted),
``runs_per_unit`` (runs that make one unit of work, or a key of the work
table), ``work_per_unit`` (a key of the work table) and ``peak``.
"""

import re


def read(ctx, params):
    trace, work = ctx["trace"], ctx["work"]
    seconds = sum(s for name, s in trace[params["line"]].items() if re.search(params["match"], name))
    runs = sum(n for name, n in trace["module_runs"].items() if re.search(params["runs_of"], name))
    if seconds <= 0 or runs <= 0:
        return None
    per_unit = params.get("runs_per_unit", 1)
    units = runs / (work[per_unit] if isinstance(per_unit, str) else per_unit)
    least = units * work[params["work_per_unit"]] / ctx["peaks"][params["peak"]]
    return 100.0 * least / seconds

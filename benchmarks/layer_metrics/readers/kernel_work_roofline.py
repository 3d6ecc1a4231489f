"""Roofline share of a kernel whose work only the device knows: the least
time the chip could take — the larger of its operations over the peak
FLOP/s and its bytes over the peak bytes/s — for the work the program
counted in the traced window, over the device time of the kernel's runs
in that window.

The work is the family's to say, whatever implements it:
``params["flops_fn"]`` names a function ``(model, units) -> FLOPs`` and
``params["bytes_fn"]`` one ``(model, calls) -> bytes`` of the family of
the configuration ``params["config"]`` (a name in ``BENCHMARK.json``),
loaded through ``spec.load_family``. ``units`` and ``calls`` are the
program's own counts: ``rows`` and ``calls`` of stage ``params["stage"]``
of ``pathway_tpu.tracing.stage_totals()``. ``match`` is a regular
expression on the device operation's name (``op_s``), shape included, so
that only the runs the stage counted are timed. ``None`` where the
program has no such stage or the trace no such operation.
"""

import json
import os
import re


def read(ctx, params):
    seconds = sum(s for name, s in ctx["trace"]["op_s"].items() if re.search(params["match"], name))
    if seconds <= 0:
        return None
    try:
        from pathway_tpu.tracing import stage_totals
    except ImportError:  # a program from before the stages
        return None
    stage = stage_totals().get(params["stage"], {})
    units, calls = stage.get("rows", 0), stage.get("calls", 0)
    if units <= 0 or calls <= 0:
        return None
    from benchmarks.lib import spec

    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(spec.ROOT, files[params["config"]]), encoding="utf-8") as f:
        model = json.load(f)["model"]
    family = spec.load_family(model["family"])
    peaks = ctx["peaks"]
    least = max(
        getattr(family, params["flops_fn"])(model, units) / peaks["bf16_flops_per_s"],
        getattr(family, params["bytes_fn"])(model, calls) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds

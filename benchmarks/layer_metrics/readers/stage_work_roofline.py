"""Roofline share of a compiled program whose runs do unequal work: the
least time the chip could take for the work the program itself counted
in the traced window, over the device time of the program's runs in that
window.

``program_roofline`` multiplies the runs by one batch's work; where every
run has another (a stream of whole documents), the work is read from the
stage that counted it instead: ``tokens`` and ``rows`` of stage
``params["stage"]`` of ``pathway_tpu.tracing.stage_totals()``, handed to
``params["flops_fn"]`` — a function ``(model, tokens, rows) -> FLOPs`` of
the family of the configuration ``params["config"]`` (a name in
``BENCHMARK.json``), loaded through ``spec.load_family``. ``line`` and
``match`` choose the device seconds as in ``program_roofline``. ``None``
where the program has no such stage or the trace no such program.
"""

import json
import os
import re


def read(ctx, params):
    seconds = sum(s for name, s in ctx["trace"][params["line"]].items() if re.search(params["match"], name))
    if seconds <= 0:
        return None
    try:
        from pathway_tpu.tracing import stage_totals
    except ImportError:  # a program from before the stages
        return None
    stage = stage_totals().get(params["stage"], {})
    tokens, rows = stage.get("tokens", 0), stage.get("rows", 0)
    if tokens <= 0:
        return None
    from benchmarks.lib import spec

    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(spec.ROOT, files[params["config"]]), encoding="utf-8") as f:
        model = json.load(f)["model"]
    flops = getattr(spec.load_family(model["family"]), params["flops_fn"])(model, tokens, rows)
    return 100.0 * flops / ctx["peaks"][params["peak"]] / seconds

"""Roofline share of a kernel by bytes: the least time the chip's memory
could take to move what the kernel has to move for the real tokens the
program embedded in the traced window, over the device time of the
kernel's runs in that window.

The bytes are the family's to say: ``params["bytes_fn"]`` names a function
``(model, tokens) -> bytes`` of the family of the configuration
``params["config"]`` (a name in ``BENCHMARK.json``), loaded through
``spec.load_family``. The tokens are the program's own count — stage
``params["tokens_stage"]`` of ``pathway_tpu.tracing.stage_totals()``, real
tokens, padding left out — so the share falls with padding as well as
with a slow kernel. ``match`` is a regular expression on the device
operation's name (``op_s``), shape included, so that only the runs of the
shape it names are counted. ``None`` where the program has no such stage
or the trace no such operation.

A kernel whose work is elementwise (VPU/EUP) has no published peak in the
table; held to the bytes it moves it reads low, and says how far the
kernel is from being free, not how well it uses the vector units.
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
    tokens = stage_totals().get(params["tokens_stage"], {}).get("tokens", 0)
    if tokens <= 0:
        return None
    from benchmarks.lib import spec

    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(spec.ROOT, files[params["config"]]), encoding="utf-8") as f:
        model = json.load(f)["model"]
    least_bytes = getattr(spec.load_family(model["family"]), params["bytes_fn"])(model, tokens)
    return 100.0 * least_bytes / ctx["peaks"][params["peak"]] / seconds

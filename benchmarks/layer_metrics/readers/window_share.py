"""Work done over the whole window as a share of what the chip could do
in that time. params: ``work`` (a key of the run's work table) and
``peak`` (a key of the peaks table)."""


def read(ctx, params):
    work = ctx["work"].get(params["work"], 0.0)
    if work <= 0:
        return None
    return 100.0 * work / (ctx["window_s"] * ctx["peaks"][params["peak"]])

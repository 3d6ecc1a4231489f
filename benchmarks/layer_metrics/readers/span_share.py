"""Share of the wall of some of the benchmark's spans in that of others.
params: ``numerator`` and ``denominator``, lists of span names."""


def read(ctx, params):
    total = {"numerator": 0.0, "denominator": 0.0}
    for name, t0, t1 in ctx["spans"]:
        for side in total:
            if name in params[side]:
                total[side] += t1 - t0
    if total["denominator"] <= 0:
        return None
    return 100.0 * total["numerator"] / total["denominator"]

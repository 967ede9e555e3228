"""Mean duration of a span, in ms; with ``per`` the summed duration over the
sum of that span argument (``fastsync.apply`` over its ``n`` blocks)."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    spans = spans_named(data, args["span"], args.get("where"))
    if not spans:
        return None
    total_ms = sum(sp["t1"] - sp["t0"] for sp in spans) / 1e6
    if "per" in args:
        units = sum(float(sp["args"].get(args["per"], 0)) for sp in spans)
        return total_ms / units if units else None
    return total_ms / len(spans)

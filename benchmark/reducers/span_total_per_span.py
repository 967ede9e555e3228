"""Summed duration of the spans called ``span`` (``where`` narrows them) over
the number of spans called ``per_span``, in ms: what a step that runs in only
some dispatches, or several times in one, costs a dispatch on average.
Nothing where the program draws no ``span`` at all (it never ran: a mean over
it would be a guess), or no ``per_span``."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    spans = spans_named(data, args["span"], args.get("where"))
    per = spans_named(data, args["per_span"])
    if not spans or not per:
        return None
    return sum(sp["t1"] - sp["t0"] for sp in spans) / 1e6 / len(per)

"""Share (in %) of the spans called ``span`` whose args include ``where``."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    every = spans_named(data, args["span"])
    if not every:
        return None
    return 100.0 * len(spans_named(data, args["span"], args["where"])) / len(every)

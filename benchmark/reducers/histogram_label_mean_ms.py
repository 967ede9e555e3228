"""Mean of the observations of ONE series of a labelled histogram family
over the window, in ms: the growth of the ``_sum`` (seconds) whose labels
include ``labels`` over the growth of the matching ``_count``, times 1,000.
``histogram_mean_ms`` sums a family's series; this reads one of them (a
stage of a block's apply).  Nothing where the program has no such series,
or where it observed nothing in the window."""

from benchmark.harness import counter_sum


def reduce(args, data):
    labels = args["labels"]
    count = counter_sum(data.counters, args["histogram"] + "_count", labels)
    if not count:
        return None
    return 1e3 * counter_sum(data.counters, args["histogram"] + "_sum", labels) / count

"""Mean of a histogram family's observations over the window, in ms: the
growth of its ``_sum`` (seconds) over the growth of its ``_count``, times
1,000.  Nothing where the program has no such family, or where it observed
nothing in the window."""

from benchmark.harness import counter_sum


def reduce(args, data):
    count = counter_sum(data.counters, args["histogram"] + "_count")
    if not count:
        return None
    return 1e3 * counter_sum(data.counters, args["histogram"] + "_sum") / count

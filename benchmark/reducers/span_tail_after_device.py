"""How long a host span went on after the device was done: the span's end
less the end of the last device operation (profiler trace, the spans' clock)
that ended inside it; mean in ms over the spans called ``span`` that hold
one.  The result was ready and the waiting thread had not run yet.  Nothing
without a trace, or where no operation ended inside such a span."""

from bisect import bisect_right

from benchmark.reducers._common import spans_named


def reduce(args, data):
    if not data.trace or not data.trace["ops"]:
        return None
    ends = sorted(start + dur for _name, start, dur, _dev in data.trace["ops"])
    tails = []
    for sp in spans_named(data, args["span"]):
        i = bisect_right(ends, sp["t1"]) - 1
        if i >= 0 and ends[i] >= sp["t0"]:
            tails.append(sp["t1"] - ends[i])
    if not tails:
        return None
    return sum(tails) / 1e6 / len(tails)

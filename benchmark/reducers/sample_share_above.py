"""The share (0..1) of the window's samples that lie above ``times_median``
times the window's own median: how many calls took the slow way, where a
program has one.  A one-mode program reads 0.0; no samples read nothing."""

from benchmark.harness import percentile


def reduce(args, data):
    values = data.samples.get(args["samples"])
    if not values:
        return None
    edge = float(args["times_median"]) * percentile(values, 50)
    return sum(v > edge for v in values) / len(values)

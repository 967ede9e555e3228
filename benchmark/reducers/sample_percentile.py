"""The q-th percentile over every sample of the window."""

from benchmark.harness import percentile


def reduce(args, data):
    values = data.samples.get(args["samples"])
    if not values:
        return None
    return percentile(values, float(args["q"]))

"""One total of the window over another: work over the seconds it took."""

from benchmark.harness import rate


def reduce(args, data):
    if args["count"] not in data.totals or not data.totals.get(args["seconds"]):
        return None
    return rate(data.totals[args["count"]], data.totals[args["seconds"]])

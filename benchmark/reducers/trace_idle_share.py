"""1 - (union of device-operation intervals) / traced window, in %."""

from benchmark import tracefile


def reduce(args, data):
    if not data.trace:
        return None
    return tracefile.idle_share(data.trace)

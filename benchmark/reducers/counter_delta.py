"""A counter family's growth over the window (series summed; ``labels``
narrows them).  A family that never appeared reads 0 only if ``default0``."""

from benchmark.harness import counter_sum


def reduce(args, data):
    if not data.counters:
        return None
    family = args["counter"]
    present = any(k.partition("{")[0] == family for k in data.counters)
    if not present and not args.get("default0"):
        return None
    return counter_sum(data.counters, family, args.get("labels"))

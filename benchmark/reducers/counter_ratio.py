"""One counter family's growth over another's (lanes audited per dispatch,
heights per window: a histogram's ``_sum`` over its ``_count``)."""

from benchmark.harness import counter_sum


def reduce(args, data):
    den = counter_sum(data.counters, args["denominator"], args.get("denominator_labels"))
    if not den:
        return None
    num = counter_sum(data.counters, args["numerator"], args.get("numerator_labels"))
    return num / den

"""Device time of the operations whose name matches ``pattern``, from the
profiler's trace, in ms per ``verify.dispatch`` span of the traced window."""

from benchmark import tracefile
from benchmark.reducers._common import spans_named


def reduce(args, data):
    if not data.trace or not data.trace["ops"]:
        return None
    seconds, count = tracefile.kernel_seconds(data.trace, args["pattern"])
    dispatches = len(spans_named(data, args.get("per_span", "verify.dispatch")))
    if not count or not dispatches:
        return None
    return seconds * 1e3 / dispatches

"""``ops_over_time`` for a kernel whose operation count lives in a module of
its own: ``module`` names ``benchmark/<module>.py``, whose ``FUNCTIONS``
table holds ``function`` (work the lanes need, fed the signatures the window
dispatched).  Over the device time of the operations matching ``pattern``;
``scale`` turns it into the unit, ``peak`` into a share (in %) of that entry
of ``benchmark/peaks.json``.  Nothing without a trace, without a matching
operation or without lanes."""

import importlib

from benchmark import tracefile
from benchmark.harness import counter_sum


def reduce(args, data):
    if not data.trace or not data.trace["ops"]:
        return None
    seconds, count = tracefile.kernel_seconds(data.trace, args["pattern"])
    lanes = counter_sum(data.counters, args["lanes_counter"], args.get("lanes_labels"))
    if not count or not seconds or not lanes:
        return None
    table = importlib.import_module("benchmark." + args["module"]).FUNCTIONS
    per_second = table[args["function"]](lanes) / seconds
    if "peak" in args:
        return 100.0 * per_second / data.bench.peaks(data.device_kind)[args["peak"]]
    return per_second * float(args.get("scale", 1.0))

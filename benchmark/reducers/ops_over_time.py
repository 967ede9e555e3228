"""Work the lanes need (a function of ``benchmark/opcount.py``, fed the
signatures the window dispatched) over the kernel's device time.

``scale`` turns it into the unit (1e-9 for G../s).  With ``peak`` the result
is the share (in %) of that entry of ``benchmark/peaks.json`` for this device
kind: the least time the chip could take over the time it took."""

from benchmark import opcount, tracefile
from benchmark.harness import counter_sum


def reduce(args, data):
    if not data.trace or not data.trace["ops"]:
        return None
    seconds, count = tracefile.kernel_seconds(data.trace, args["pattern"])
    lanes = counter_sum(data.counters, args["lanes_counter"], args.get("lanes_labels"))
    if not count or not seconds or not lanes:
        return None
    work = opcount.FUNCTIONS[args["function"]](lanes)
    per_second = work / seconds
    if "peak" in args:
        peak = data.bench.peaks(data.device_kind)[args["peak"]]
        return 100.0 * per_second / peak
    return per_second * float(args.get("scale", 1.0))

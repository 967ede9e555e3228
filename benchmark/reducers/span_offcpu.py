"""How long a span's thread was off the CPU inside it: the span's duration
less its ``args.cpu_ms`` (the thread's CPU time between entry and exit, which
the program's tracer records), mean in ms over the spans called ``span``
(``where`` narrows them); with ``per`` the summed difference over the sum of
that span argument.  In a span of plain Python that is the wait for the
interpreter lock and the scheduler; in a span drawn round a wait, the wait.
Nothing where no such span carries ``cpu_ms`` (a program whose tracer records
none)."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    spans = [sp for sp in spans_named(data, args["span"], args.get("where"))
             if "cpu_ms" in sp["args"]]
    if not spans:
        return None
    off_ms = sum((sp["t1"] - sp["t0"]) / 1e6 - float(sp["args"]["cpu_ms"])
                 for sp in spans)
    if "per" in args:
        units = sum(float(sp["args"].get(args["per"], 0)) for sp in spans)
        return off_ms / units if units else None
    return off_ms / len(spans)

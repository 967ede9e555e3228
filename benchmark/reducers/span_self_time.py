"""A span's self time by identity: its duration less the union of its direct
children (the spans whose ``args.parent_id`` is its ``args.span_id``, on any
thread), mean over the spans called ``span``, in ms.  Nothing where the
program draws no ids."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    parents = [p for p in spans_named(data, args["span"]) if "span_id" in p["args"]]
    if not parents:
        return None
    children = {}
    for sp in data.spans:
        if sp["args"].get("parent_id") is not None:
            children.setdefault(sp["args"]["parent_id"], []).append(sp)
    total = 0
    for p in parents:
        end = p["t0"]  # the children cover [.., end) of the parent so far
        own = p["t1"] - p["t0"]
        for c in sorted(children.get(p["args"]["span_id"], ()), key=lambda s: s["t0"]):
            s, e = max(c["t0"], end), min(c["t1"], p["t1"])
            if e > s:
                own -= e - s
                end = e
        total += own
    return total / 1e6 / len(parents)

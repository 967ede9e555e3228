"""A span's self time: its duration less what the ``child`` spans inside it
cover (on any thread: a guarded dispatch runs on a worker), mean per parent
span, in ms."""

from benchmark.reducers._common import spans_named


def reduce(args, data):
    parents = spans_named(data, args["span"])
    if not parents:
        return None
    children = sorted(spans_named(data, args["child"]), key=lambda s: s["t0"])
    total = 0
    j = 0
    for p in sorted(parents, key=lambda s: s["t0"]):
        inside = 0
        while j < len(children) and children[j]["t1"] <= p["t0"]:
            j += 1
        k = j
        while k < len(children) and children[k]["t0"] < p["t1"]:
            inside += min(children[k]["t1"], p["t1"]) - max(children[k]["t0"], p["t0"])
            k += 1
        total += (p["t1"] - p["t0"]) - inside
    return total / 1e6 / len(parents)

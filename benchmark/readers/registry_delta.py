"""A per-layer metric from the program's metric registry: the change of a
counter, or of a histogram's sum or count, between two of the run's marks,
optionally over another such change or over the seconds between the marks.

args: ``over`` [from mark, to mark] (marks: process_start, window_start,
window_end); ``num`` and optional ``den``, each {"counter": name} |
{"hist_sum": name} | {"hist_count": name} | {"seconds": true};
``scale`` (default 1).  Nothing recorded under a name at the later mark:
nothing to read.
"""


def _delta(spec, registry, marks, a, b):
    if spec.get("seconds"):
        return marks[b] - marks[a]
    kind, name = next(iter(spec.items()))
    field = {"hist_sum": "sum", "hist_count": "n"}.get(kind)

    def at(mark):
        snap = registry.get(mark)
        if snap is None:                      # process_start: nothing yet
            return 0.0
        if field is None:
            return snap["counters"].get(name)
        return snap["hists"].get(name, {}).get(field)

    end = at(b)
    if end is None:
        return None
    return end - (at(a) or 0.0)


def read(args, evidence):
    a, b = args["over"]
    reg, marks = evidence["registry"], evidence["marks"]
    if b not in reg:
        return None
    num = _delta(args["num"], reg, marks, a, b)
    if num is None:
        return None
    if "den" in args:
        den = _delta(args["den"], reg, marks, a, b)
        if not den:
            return None
        num /= den
    return num * args.get("scale", 1.0)

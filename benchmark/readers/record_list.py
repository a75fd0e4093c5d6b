"""One number from the list fields of a record the program wrote into the
warm-up job's sink (one entry a superstep, or a level). ``select`` is a
pattern, as ``phase_seconds`` takes them; the newest record of scope
``warmup`` that matches is read. ``field`` names the list, ``where`` (``field``
and ``equals`` or ``differs``) keeps the places at which a second list of the
record equals, or differs from, a value, and ``reduce`` is

- ``median``: the median of the kept entries; nothing where none is kept;
- ``median_over_first``: that median over the list's first entry (the first
  superstep's count, which is the whole plan's);
- ``share``: the kept places over all places; ``0.0`` where none is kept.

``scale`` multiplies the result. A program that writes no such record, or a
record without these lists, gives nothing to read."""

import statistics


def read(args: dict, run: dict):
    picked = [r for r in run["records"] if r.get("scope") == "warmup"
              and all(r.get(k) == v for k, v in args["select"].items())]
    if not picked:
        return None
    record, where = picked[-1], args["where"]
    values, marks = record.get(args["field"]), record.get(where["field"])
    if not values or not marks or len(values) != len(marks):
        return None
    if "equals" in where:
        kept = [v for v, m in zip(values, marks) if m == where["equals"]]
    else:
        kept = [v for v, m in zip(values, marks) if m != where["differs"]]
    if args["reduce"] == "share":
        value = len(kept) / len(values)
    elif not kept:
        return None
    elif args["reduce"] == "median":
        value = statistics.median(kept)
    elif args["reduce"] == "median_over_first" and values[0]:
        value = statistics.median(kept) / values[0]
    else:
        return None
    return args.get("scale", 1.0) * value

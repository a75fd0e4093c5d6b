"""One number from a field of the records of one kind that the program wrote
into the warm-up job's sink (a record a compiled program, say). ``select`` is
a pattern, as ``phase_seconds`` takes them; every record of scope ``warmup``
that matches it and holds a number under ``field`` (and under ``minus``,
where that names a second field) counts. A record's value is its ``field``,
less its ``minus`` where one is named. ``reduce`` is

- ``sum``: the values' sum;
- ``max``: the largest value;
- ``at_largest``: the value of the record whose ``field`` is the largest
  (with ``minus``: a signed difference, taken where ``field`` weighs most).

``scale`` multiplies the result. A program that writes no such record gives
nothing to read."""


def read(args: dict, run: dict):
    fields = [args["field"], *([args["minus"]] if "minus" in args else [])]
    number = lambda x: isinstance(x, (int, float)) and not isinstance(x, bool)
    picked = [r for r in run["records"] if r.get("scope") == "warmup"
              and all(r.get(k) == v for k, v in args["select"].items())
              and all(number(r.get(f)) for f in fields)]
    if not picked:
        return None
    value = lambda r: r[fields[0]] - (r[fields[1]] if len(fields) > 1 else 0)
    if args["reduce"] == "at_largest":
        got = value(max(picked, key=lambda r: r[fields[0]]))
    else:
        got = {"sum": sum, "max": max}[args["reduce"]](value(r) for r in picked)
    return args.get("scale", 1.0) * got

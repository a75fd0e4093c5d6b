"""Seconds of the selected records among those that could have been
selected. ``select`` and ``among`` are lists of patterns, as ``phase_seconds``
takes them, and ``scope`` is the records' (``warmup``: the warm-up job's
program records, summed once). Where a record matches ``among`` and none
matches ``select`` the value is ``0.0``: the program wrote its ``compile``
records and not one was a load from the persistent cache. Where no record
matches ``among`` there is nothing to read: a program that writes no such
record is not one that loaded nothing."""


def _matches(record: dict, patterns: list) -> bool:
    return any(all(record.get(k) == v for k, v in pattern.items())
               for pattern in patterns)


def read(args: dict, run: dict):
    among = [r for r in run["records"]
             if r.get("scope") == args["scope"] and "seconds" in r
             and _matches(r, args["among"])]
    if not among:
        return None
    return float(sum(r["seconds"] for r in among if _matches(r, args["select"])))

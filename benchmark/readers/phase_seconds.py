"""Seconds of the selected records, from what the driver hands over: the
program's own ``MetricsSink`` records and the harness's clock around calls
into a layer. ``select`` is a list of patterns; a record counts when every
key of one pattern equals the record's. ``scope`` is ``job`` (mean per job
of the window) or ``setup`` (summed once)."""


def read(args: dict, run: dict):
    scope = args.get("scope", "job")
    picked = [
        r["seconds"] for r in run["records"]
        if r.get("scope") == scope and "seconds" in r
        and any(all(r.get(k) == v for k, v in pattern.items())
                for pattern in args["select"])
    ]
    if not picked:
        return None
    total = float(sum(picked))
    return total / len(run["jobs"]) if scope == "job" else total

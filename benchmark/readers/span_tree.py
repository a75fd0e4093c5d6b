"""Seconds from the program's span tree, the mean per job of the window.

``parent`` names a span of the job (``outliers_lof``). With ``stages``
(a list of span names) the value is the summed seconds of the ``span``
records with those names whose ``span_path`` lies anywhere under the
parent's: the stages sit below ``run/outliers_lof/rung:primary``, not
directly below the chapter. It is ``0.0`` when the parent ran with other
stages under it and none of these (the exact-kNN path has no ``ivf_*``
stage: zero seconds, not "nothing to read"), and ``None`` when no record
of the job carries the parent's path or no span at all lies under it (a
program that names no stage there has nothing to read). With ``"mode": "self"`` the value is the parent span's own
seconds minus those of the stages under it: the chapter's time that no
stage names. That subtraction is sound only while the listed stages run
one after another and none contains another, which is checked from their
paths. With ``records`` (a pattern, as ``phase_seconds`` takes them)
instead of ``stages``, the value is the summed ``seconds`` of the
non-span records that match and whose ``span_path`` is the parent's or
lies under it.
"""


def _under(path: str, parent: str) -> bool:
    return path.startswith(parent + "/")


def _job_value(args: dict, records: list):
    name = args["parent"]
    spans = [r for r in records if r.get("phase") == "span"]
    parents = [r for r in spans if r.get("name") == name]
    if not parents:
        # the root span writes no record: it is found by the paths under it
        paths = {r.get("span_path", "") for r in records}
        if not any(p == name or _under(p, name) for p in paths):
            return None
        parent_paths, parent_seconds = [name], None
    else:
        parent_paths = [r["span_path"] for r in parents]
        parent_seconds = sum(r["seconds"] for r in parents)

    def below(path, or_at=False):
        return any(_under(path, p) or (or_at and path == p) for p in parent_paths)

    if "records" in args:
        return float(sum(
            r["seconds"] for r in records
            if r.get("phase") != "span" and "seconds" in r
            and below(r.get("span_path", ""), or_at=True)
            and all(r.get(k) == v for k, v in args["records"].items())
        ))
    if not any(below(r["span_path"]) for r in spans):
        return None
    stages = [r for r in spans
              if r.get("name") in args["stages"] and below(r["span_path"])]
    total = float(sum(r["seconds"] for r in stages))
    if args.get("mode") != "self":
        return total
    for a in stages:
        if any(_under(b["span_path"], a["span_path"]) for b in stages):
            raise ValueError(
                f"span_tree: stage {a['span_path']} contains another listed "
                "stage; the parent's self time would count it twice")
    return None if parent_seconds is None else parent_seconds - total


def read(args: dict, run: dict):
    by_job: dict = {}
    for r in run["records"]:
        if r.get("scope") == "job":
            by_job.setdefault(r["job"], []).append(r)
    values = [_job_value(args, records) for records in by_job.values()]
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None

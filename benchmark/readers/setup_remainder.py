"""Set-up's seconds that no stage names: the run's ``setup_s`` (process start
to the window, ``run.py``'s own reading) minus the ``seconds`` of the records
of scope ``setup`` whose ``phase`` is one of ``stages``. The listed stages run
one after another and none holds another (``plan_build`` and ``partition``
lie inside ``warmup_job`` and are not listed). A driver that states fewer
stages leaves more here; a run that states no ``setup_s`` has nothing to
read."""


def read(args: dict, run: dict):
    if run.get("setup_s") is None:
        return None
    named = sum(r["seconds"] for r in run["records"]
                if r.get("scope") == "setup" and r.get("phase") in args["stages"])
    return run["setup_s"] - named

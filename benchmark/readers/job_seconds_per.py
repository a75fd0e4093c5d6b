"""Mean seconds of one job, divided by a count the driver states among its
facts (``per``, say the supersteps of a job) and scaled (1000 for ms)."""


def read(args: dict, run: dict):
    if not run["jobs"]:
        return None
    mean = sum(j["seconds"] for j in run["jobs"]) / len(run["jobs"])
    return args.get("scale", 1.0) * mean / run["facts"][args["per"]]

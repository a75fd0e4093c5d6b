"""A sharded kernel's share of its roofline: the least bytes ONE chip needs
for one call (``benchmark/roofline_mesh.py``, fed the driver's facts named
in ``bytes_args``, the last of them the chip count), over that chip's
published HBM peak, divided by the device-busy seconds of one call from the
trace, which the reduction already averages over the chips."""

import roofline_mesh  # benchmark/roofline_mesh.py: run.py puts its directory on the path


def read(args: dict, run: dict):
    trace, facts = run["trace"], run["facts"]
    if not trace or trace["busy_s"] <= 0 or not run["jobs"]:
        return None
    needed = args["bytes_args"] + [args["calls_per_job"]]
    if any(name not in facts for name in needed):
        return None
    min_bytes = getattr(roofline_mesh, args["bytes_function"])(
        *[facts[name] for name in args["bytes_args"]])
    calls = len(run["jobs"]) * facts[args["calls_per_job"]]
    return roofline_mesh.share_percent(
        min_bytes, trace["busy_s"] / calls, run["device"]["kind"])

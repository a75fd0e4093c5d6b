"""A kernel's share of its roofline: the least bytes the kernel needs for
one call, from shapes (the function ``bytes_function`` of the module
``bytes_module``, a file of ``benchmark/`` beside ``roofline.py``, which is
the default; fed the driver's facts named in ``bytes_args``), over the
chip's published HBM peak, divided by the device-busy seconds of one call
from the trace (``calls_per_job`` names the fact that counts calls). The
peaks and the share's arithmetic are ``roofline.py``'s whatever module
counts the bytes."""

import importlib

import roofline  # benchmark/roofline.py: run.py puts its own directory on the path


def read(args: dict, run: dict):
    trace, facts = run["trace"], run["facts"]
    if not trace or trace["busy_s"] <= 0 or not run["jobs"]:
        return None
    counts = importlib.import_module(args.get("bytes_module", "roofline"))
    min_bytes = getattr(counts, args["bytes_function"])(
        *[facts[name] for name in args["bytes_args"]])
    calls = len(run["jobs"]) * facts[args["calls_per_job"]]
    return roofline.roofline_share_percent(
        min_bytes, trace["busy_s"] / calls, run["device"]["kind"])

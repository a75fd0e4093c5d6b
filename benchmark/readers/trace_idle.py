"""Share of the traced window in which no operation ran on the device,
averaged over the chips used, from the harness's own profiler trace."""


def read(args: dict, run: dict):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Peak bytes in use on the fullest chip over that chip's ``bytes_limit``,
read from the device after the window."""


def read(args: dict, run: dict):
    memory = run["memory"]
    if not memory["memory_peak_bytes"] or not memory["memory_limit_bytes"]:
        return None
    return 100.0 * memory["memory_peak_bytes"] / memory["memory_limit_bytes"]

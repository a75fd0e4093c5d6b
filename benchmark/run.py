#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
its traffic mix, its driver and its per-layer metrics are files found by
the names written there (see ``benchmark/README.md``). This file knows no
cell by name.

A run is: set-up (generate from the seed, build, compile or load from the
compile cache, one untimed warm-up job), the measured window (whole jobs
back to back), the correctness comparisons (outside the window and outside
``setup_s``), and ONE last line of JSON on standard output. Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no
result. ``--rehearse`` runs the same code at the tiny sizes of the
configuration's ``rehearsal`` block on any backend, exits 4 and prints no
result. ``--control`` makes the comparisons twice, the second time with the
cell's control (a reference that breaks one stated guarantee or computes in
the next lower precision) in the program's place, exits 5 and prints no
result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is everything from here to the window

import argparse
import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)  # drivers and readers import the yardstick's modules by name
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_COMPILES = {"count": 0, "seconds": 0.0, "cache_hits": 0}


def say(**record) -> None:
    print(json.dumps(record, default=str), flush=True)


def _named(name: str) -> str:
    if not _NAME.match(name):
        raise SystemExit(f"run.py: {name!r} is not a valid name")
    return name


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, loaded by file so that a later PR
    adds a driver, a reader or an algorithm as a file and edits nothing."""
    path = os.path.join(HERE, kind, _named(name) + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> dict:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    data = os.path.join(root, bench["paths"][0])
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [
        dict(m, **_read_json(
            os.path.join(data, "layer_metrics", _named(m["name"]) + ".json")))
        for m in bench["per_layer"]
        if _reports(m, workload) and m["moves"] in moved
    ]
    return {
        "name": workload,
        "chips": cell["chips"],
        "config": _read_json(os.path.join(root, entry["file"])),
        "traffic": _read_json(
            os.path.join(data, "traffic", _named(cell["traffic"]) + ".json")),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def _listen_for_compiles() -> None:
    import jax

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["seconds"] += seconds
            _COMPILES["count"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _memory(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    limits = [s.get("bytes_limit") for s in stats]
    return {
        "memory_peak_bytes": max(peaks) if all(p is not None for p in peaks) else None,
        "memory_limit_bytes": min(limits) if all(limits) else None,
    }


def _start_trace(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # annotations only: the tracer must not slow the host
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def run_window(driver, state, seconds: float, max_jobs: int | None):
    """Whole jobs back to back. A new job starts only while the time spent
    plus the last job's seconds still fits ``seconds``; one always runs."""
    import jax

    jobs, failed = [], 0
    opened = time.perf_counter()
    while True:
        try:
            with jax.profiler.TraceAnnotation("bench_job"):
                job = driver.job(state, len(jobs))
        except Exception:
            traceback.print_exc()
            failed += 1
            break
        jobs.append(job)
        if len(jobs) <= 8:  # a rehearsal fits hundreds of tiny jobs
            say(job=len(jobs) - 1, seconds=job["seconds"],
                bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                              for d in jax.local_devices()])
        elapsed = time.perf_counter() - opened
        if max_jobs is not None and len(jobs) >= max_jobs:
            break
        if elapsed + job["seconds"] > seconds:
            break
    return jobs, failed, time.perf_counter() - opened


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend; exits 4, prints no result")
    ap.add_argument("--control", action="store_true",
                    help="compare the cell's control instead; exits 5, no result")
    ap.add_argument("--root", default=CHECKOUT,
                    help="directory holding BENCHMARK.json and the data files")
    args = ap.parse_args()

    cell = load_cell(args.root, args.workload)
    driver = load_module("drivers", cell["traffic"]["driver"])
    readers = {m["name"]: load_module("readers", m["reader"])
               for m in cell["per_layer"]}

    sys.path.insert(0, CHECKOUT)
    import graphmine_tpu

    if os.path.dirname(os.path.dirname(os.path.abspath(graphmine_tpu.__file__))) != CHECKOUT:
        print("run.py: the program is not in this checkout", file=sys.stderr)
        return 2
    import jax

    t_imported = time.perf_counter()
    devices = jax.devices()
    if not args.rehearse and (
        devices[0].platform != "tpu" or len(devices) < cell["chips"]
    ):
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices())}

    from graphmine_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import handover  # benchmark/handover.py: the records' one shape

    started = {"process_start": t_imported - _T0,
               "backend_start": time.perf_counter() - t_imported}
    own_stages = handover.stages(**started)
    _listen_for_compiles()
    say(workload=args.workload, seed=args.seed, device=device, cache_dir=cache_dir,
        **{phase + "_s": s for phase, s in started.items()})

    scratch = tempfile.mkdtemp(prefix="bench_")
    try:
        ctx = {
            "config": cell["config"], "traffic": cell["traffic"],
            "sizes": cell["config"]["rehearsal"] if args.rehearse else cell["config"],
            "seed": args.seed % (1 << 63), "scratch": scratch,
            "chips": cell["chips"], "say": say, "load_module": load_module,
        }
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - _T0
        compiled_in_setup = dict(_COMPILES)
        say(setup_s=setup_s, compiles=compiled_in_setup)

        trace = None
        if args.trace:
            import trace_reduce

            trace_dir = os.path.join(scratch, "trace")
            _start_trace(trace_dir)
            annotate = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION)
            max_jobs = cell["traffic"].get("traced_jobs", 1)
        else:
            annotate, max_jobs = contextlib.nullcontext(), None
        with annotate:
            jobs, failed, window_s = run_window(driver, state, args.seconds, max_jobs)
        if args.trace:
            jax.profiler.stop_trace()
            trace = trace_reduce.reduce_events(
                *trace_reduce.read_xplane(trace_reduce.newest_xplane(trace_dir)))
            shutil.rmtree(trace_dir, ignore_errors=True)
        compiles_in_window = _COMPILES["count"] - compiled_in_setup["count"]
        compile_s_in_window = _COMPILES["seconds"] - compiled_in_setup["seconds"]
        memory = _memory(devices)
        say(window_s=window_s, jobs=len(jobs), failed=failed,
            compiles_in_window=compiles_in_window,
            compile_seconds_in_window=compile_s_in_window, **memory)

        t_check = time.perf_counter()
        checks = driver.check(state, jobs, False) if jobs else []
        for c in checks:
            say(**c)
        if args.control and jobs:
            say(sound_run_correct=all(c["ok"] for c in checks))
            checks = driver.check(state, jobs, True)
            for c in checks:
                say(control=True, **c)
        say(check_seconds=time.perf_counter() - t_check)
        correct = bool(jobs) and failed == 0 and all(c["ok"] for c in checks)

        run = {
            "jobs": jobs, "window_s": window_s, "trace": trace, "device": device,
            "memory": memory, "setup_s": setup_s,
            "records": driver.records(state, jobs) + own_stages,
            "facts": driver.facts(state),
        }
        if args.trace:
            metrics = {}
            for m in cell["per_layer"]:
                value = readers[m["name"]].read(m.get("args", {}), run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = dict(driver.end_to_end(state, jobs, window_s), setup_s=setup_s)
            metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in cell["end_to_end"]}
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    if args.control:
        say(control="compared", correct=correct)
        return 5
    if args.rehearse:
        say(rehearsal="passed" if correct else "failed", metrics=metrics)
        return 4 if correct else 1
    result = {
        "correct": correct, "attempted": len(jobs) + failed, "failed": failed,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=memory["memory_peak_bytes"]),
        "compiles_in_window": compiles_in_window,
    }
    if trace is not None:
        if trace["busy_s"] <= 0:
            print("run.py: the trace shows no operation on the device",
                  file=sys.stderr)
            return 3
        result["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())

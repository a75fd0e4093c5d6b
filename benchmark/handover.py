"""What every driver hands its readers, in one place.

``records(state, jobs)`` of a driver returns a list of records, each with a
``scope`` that the readers select by:

- ``setup``: seconds of set-up by the harness's clock, one record a stage
  (``stages``): the driver's ``generate``, ``count_vertices``, ``build_graph``
  and ``warmup_job`` (the pipeline's ``generate``, ``write_parquet``,
  ``warmup_job``), ``run.py``'s ``process_start`` and ``backend_start``; and
  the sums the drivers take from the warm-up job's records (``plan_build``,
  ``partition``), which lie inside ``warmup_job``;
- ``job``: one ``job`` record a timed job, and whatever records of the
  program a driver's timed jobs carry (``job_records``);
- ``warmup``: every record the program wrote into the warm-up job's sink, as
  the program wrote it (``warmup``): ``compile`` (``stage``, ``fun_name``,
  ``seconds``, ``cache_hit``), ``plan_build``, ``device_residency``,
  ``impl_selected``, ``superstep_delta``, ``partition``, ``exchange``,
  ``fixpoint``, ``superstep_timing``, spans. A copy, taken when the warm-up
  job ends: the sink is let go there and collects nothing from the window.

``program_facts`` is the carried-rows job's facts, for every driver that runs
it. Nothing here imports the program, NumPy or jax: ``kernel_job_mesh.py``
is also its one-heap children's ``__main__``.
"""

from __future__ import annotations

import statistics


def stage(phase: str, seconds: float) -> dict:
    """One stage of set-up, by the harness's clock."""
    return {"phase": phase, "seconds": seconds, "scope": "setup"}


def stages(**seconds) -> list:
    """Stages of set-up in the order given, one record each."""
    return [stage(phase, s) for phase, s in seconds.items()]


def warmup(records) -> list:
    """The warm-up job's records as the program wrote them, each a copy
    with ``scope: "warmup"`` (a record of the pipeline's JSONL too)."""
    return [dict(r, scope="warmup") for r in records]


def job_records(jobs) -> list:
    return [{"phase": "job", "seconds": j["seconds"], "scope": "job", "job": i}
            for i, j in enumerate(jobs)]


def records(state: dict, jobs, more=()) -> list:
    """What a kernel driver hands on: set-up's stages, a ``job`` record a
    timed job, ``more`` (a driver's own records of scope ``job``), then the
    warm-up job's records; a state that kept none hands none on."""
    return (state["setup_records"] + job_records(jobs) + list(more)
            + state.get("warmup_records", []))


def program_facts(records: list) -> dict:
    """What the warm-up job's records say of the device, of the scan and of
    the plan. Each fact is left out where its record, or the key it reads,
    is missing."""
    by_phase = {r["phase"]: r for r in records}
    facts = {}
    slots = by_phase.get("plan_build", {}).get("padded_slots_per_message")
    if slots is not None:
        facts["padded_slots_per_message"] = slots
    held = by_phase.get("device_residency")
    if held is not None:
        facts["scan"] = held["scan"]
        # what stays on the chip for this graph between jobs: the graph's
        # arrays, the plan and its slot index (the rows are a job's own)
        facts["resident_bytes"] = (held["graph_bytes"] + held["plan_bytes"]
                                   + held["slot_index_bytes"])
    delta = by_phase.get("superstep_delta")
    if delta is not None:
        facts["sparse_supersteps"] = sum(b != "full" for b in delta["branch"])
        # the median: the first full superstep of a warm-up job loads its
        # programs; the stateless scan writes no seconds
        full = [s for s, b in zip(delta.get("seconds", ()), delta["branch"])
                if b == "full"]
        if full:
            facts["full_superstep_seconds"] = statistics.median(full)
    return facts

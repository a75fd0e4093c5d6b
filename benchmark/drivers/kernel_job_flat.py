"""Driver ``kernel_job_flat``: ``kernel_job_large`` on a graph with no hubs.
Set-up, job, end-to-end metric, records and the comparison are that
driver's own functions (its module is loaded by path, as it loads
``kernel_job_mesh``): the same draw in a one-heap child, the same
``build_graph`` and warm-up job, the same ``malloc_trim``, the same
``threaded_lpa`` reference and one-way control.

What is wider is what ``facts()`` states of the warm-up job's program
records, for the two things a flat degree distribution changes: how the
plan's rows are padded (``padded_slots_per_message``, from the
``plan_build`` record) and what a superstep that gathers every row anew
costs when eight or nine of ten do (``full_superstep_seconds``: the median
``seconds`` of the ``superstep_delta`` record's ``full`` supersteps; the
median, because the first full superstep of a warm-up job loads its
programs). A program that writes neither states neither fact.
"""

from __future__ import annotations

import importlib.util
import os
import statistics

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_kernel_job_large_for_flat",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernel_job_large.py"))
_large = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_large)
_narrow_program_facts = _large._program_facts


def _program_facts(records: list) -> dict:
    """``kernel_job_large``'s facts and the two a flat plan adds."""
    facts = _narrow_program_facts(records)
    by_phase = {r["phase"]: r for r in records}
    slots = by_phase.get("plan_build", {}).get("padded_slots_per_message")
    if slots is not None:
        facts["padded_slots_per_message"] = slots
    delta = by_phase.get("superstep_delta", {})
    full = [s for s, b in zip(delta.get("seconds", ()), delta.get("branch", ()))
            if b == "full"]
    if full:
        facts["full_superstep_seconds"] = statistics.median(full)
    return facts


# this module's own copy of the driver: its set-up states these facts
_large._program_facts = _program_facts

setup = _large.setup
job = _large.job
end_to_end = _large.end_to_end
records = _large.records
facts = _large.facts
check = _large.check

"""Structured metrics + profiling hooks.

The reference's only observability was ``print``/``show`` calls
(``Graphframes.py:18,32,54,68,74,82,85,120``). Here every pipeline phase
emits a structured JSON record, and LPA reports the driver's headline
metric — **edges/sec/chip** per iteration (BASELINE.json ``"metric"``).

Run-correlated tracing (docs/OBSERVABILITY.md): a sink constructed with a
:class:`~graphmine_tpu.obs.spans.Tracer` stamps every record with
``run_id`` / ``trace_id`` / ``span_id`` / ``span_path``, so the
resilience machine's retry / degrade / mesh_degrade / tripwire /
checkpoint records are joinable into one causal timeline
(``tools/obs_report.py``). The sink also owns a counter/gauge
:class:`~graphmine_tpu.obs.registry.Registry` (the level surface the
heartbeat and the Prometheus textfile exporter read).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
import weakref
from dataclasses import dataclass, field

from graphmine_tpu.obs.costmodel import note_backend_compile
from graphmine_tpu.obs.registry import Registry
from graphmine_tpu.obs.spans import xla_annotation

log = logging.getLogger("graphmine_tpu")

# ---- a record per compile --------------------------------------------------
# One process-wide pair of jax.monitoring listeners, installed when the
# first sink is made in a process that has jax, forwards each compile
# stage to every sink that is open (made and not yet finalized).

_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_open_sinks: "weakref.WeakValueDictionary[int, MetricsSink]" = (
    weakref.WeakValueDictionary()
)
# per compiling thread: .cache_hit, a hit that awaits its backend stage;
# .trace, the newest trace event, which awaits its program's lowering
_listener = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False


def _on_compile_event(event: str, **_) -> None:
    # jax reports a persistent-cache hit inside the backend stage it
    # serves, on the compiling thread, before that stage's duration
    if event == _CACHE_HIT_EVENT:
        _listener.cache_hit = True


def _on_compile_duration(event: str, seconds: float, fun_name="", **_) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    if stage == "trace":
        # jax times every function it traces, the jnp helpers inside a
        # program too (2,000 events in a cold pipeline job, 60 programs),
        # and an outer trace's seconds hold the inner ones'. The outermost
        # ends last, just before its program is lowered: keep the newest
        # and write it with that lowering, three records a program.
        _listener.trace = (str(fun_name), seconds)
        return
    notes = [(stage, str(fun_name), seconds, None)]
    if stage == "lower":
        trace = getattr(_listener, "trace", None)
        if trace is not None:
            _listener.trace = None
            notes.insert(0, ("trace", *trace, None))
    else:
        notes[0] = (*notes[0][:3], getattr(_listener, "cache_hit", False))
        _listener.cache_hit = False
        note_backend_compile()
    with _listener_lock:
        sinks = list(_open_sinks.values())
    for sink in sinks:
        for note in notes:
            sink._note_compile(*note)


def _install_compile_listener() -> None:
    """Idempotent, and a no-op until jax is imported: a sink made by
    host-only tooling must not drag the runtime in. A sink made earlier
    than jax tries again at each span it opens."""
    global _listener_installed
    jax = sys.modules.get("jax")
    if _listener_installed or jax is None:
        return
    with _listener_lock:
        if _listener_installed:
            return
        jax.monitoring.register_event_listener(_on_compile_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration
        )
        _listener_installed = True


@dataclass
class MetricsSink:
    """Collects phase timings and counters; emits JSON lines via logging.

    ``stream_path``: when set, every record is ALSO appended to that file
    as it is emitted (line-buffered JSONL). Exit-time-only persistence
    would lose exactly the records that matter most — a preemption or
    OOM-kill ends the process without running any ``finally`` block, and
    those are the runs whose retry/degrade/rollback trail the operator
    needs. The stream opens in **append** mode: a resumed run reusing the
    same ``--metrics-out`` path must not clobber the prior attempt's
    trail (each run's records begin at its ``run_start`` header and carry
    its ``run_id``). A stream write failure disables streaming with one
    warning (the in-memory records remain for the exit-time fallback).

    ``tracer``: optional :class:`~graphmine_tpu.obs.spans.Tracer`; when
    set, every record carries the current span's identity. ``registry``:
    the run's counter/gauge registry (always present — callers increment
    unconditionally; it only *exports* when asked).

    ``max_records``: optional in-memory cap for **long-lived serving
    processes** (a batch run keeps the default: everything). The serve
    layer emits one ``access_log`` record per HTTP request; retaining
    them all in ``records`` would grow RSS linearly with traffic until
    the server is OOM-killed. With a cap, the oldest records are
    dropped once the list exceeds it — records already persisted by the
    live stream lose nothing on disk, and :meth:`finalize` accounts for
    the drops so it never re-appends or skips survivors. Callers doing
    exit-time-only persistence with a cap are accepting bounded memory
    over a complete exit dump (the serving CLI streams, so it never
    hits that trade).

    Emission is thread-safe (the heartbeat thread and the driver thread
    share one sink); each record is appended and streamed under one lock.
    """

    records: list = field(default_factory=list)
    stream_path: str | None = None
    tracer: object | None = None
    registry: Registry = field(default_factory=Registry, repr=False)
    max_records: int | None = None
    _stream: object = field(default=None, repr=False)
    _stream_ok: bool = field(default=True, repr=False)
    _streamed: int = field(default=0, repr=False)
    _dropped: int = field(default=0, repr=False)
    _lost: int = field(default=0, repr=False)
    _lost_warned: bool = field(default=False, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def __post_init__(self):
        with _listener_lock:
            _open_sinks[id(self)] = self
        _install_compile_listener()

    def _note_compile(self, stage, fun_name, seconds, cache_hit) -> None:
        """One compile stage, from the process-wide listener: a
        ``compile`` record stamped with the span open on the compiling
        thread, and the registry's running totals."""
        self.emit("compile", stage=stage, fun_name=fun_name,
                  seconds=round(seconds, 6), cache_hit=cache_hit)
        if stage == "backend":
            self.registry.counter(
                "graphmine_compiles_total",
                "programs compiled, or loaded from the persistent cache",
            ).inc()
        self.registry.counter(
            "graphmine_compile_seconds_total",
            "seconds in jax's trace, lowering and backend compile stages",
        ).inc(seconds)

    def emit(self, phase: str, _span=None, **kv) -> dict:
        """Append one record (and stream it). ``_span`` pins the record
        to a specific :class:`~graphmine_tpu.obs.spans.Span` instead of
        the thread-current one — used for ``span`` records, which must
        carry their *own* identity, emitted after the span closed."""
        rec = {"phase": phase, "t": time.time()}
        tr = self.tracer
        if tr is not None:
            sp = _span if _span is not None else tr.current()
            rec["run_id"] = tr.run_id
            # The SPAN's trace id, not the tracer's: a span opened with
            # remote=/new_trace= (cross-process propagation, fleet
            # requests) carries an adopted/minted trace, and records
            # emitted inside it must land in THAT trace or the stitched
            # fleet timeline falls apart at every process boundary.
            rec["trace_id"] = sp.trace_id
            rec["span_id"] = sp.span_id
            rec["span_path"] = sp.path
            if _span is not None and sp.parent_id is not None:
                rec["parent_span_id"] = sp.parent_id
        rec.update(kv)
        line = json.dumps(rec, default=str)
        log.info("%s", line)
        with self._lock:
            self.records.append(rec)
            if self.stream_path is not None and self._stream_ok:
                try:
                    if self._stream is None:
                        self._stream = open(self.stream_path, "a")
                    self._stream.write(line + "\n")
                    self._stream.flush()
                    self._streamed += 1
                except OSError as e:
                    self._stream_ok = False
                    log.warning(
                        "metrics stream to %s failed: %r; records will be "
                        "written at exit instead", self.stream_path, e,
                    )
            if (
                self.max_records is not None
                and len(self.records) > self.max_records
            ):
                drop = len(self.records) - self.max_records
                # Dropped records with a global index past the streamed
                # prefix were never persisted anywhere — count them and
                # say so ONCE, or the 'written at exit instead' promise
                # emit makes when the stream dies becomes a silent lie
                # under the cap.
                lost = max(
                    0,
                    (self._dropped + drop)
                    - max(self._streamed, self._dropped),
                )
                del self.records[:drop]
                self._dropped += drop
                if lost:
                    self._lost += lost
                    if not self._lost_warned:
                        self._lost_warned = True
                        log.warning(
                            "max_records=%d dropped record(s) the stream "
                            "never persisted (running total tracked; "
                            "%d so far) — they will NOT appear in any "
                            "exit-time dump", self.max_records, self._lost,
                        )
        return rec

    @contextlib.contextmanager
    def timed(self, phase: str, **kv):
        """Timed phase record. When the body raises, the record keeps its
        failure identity — ``ok=false`` plus ``error`` (the classified
        kind from the resilience taxonomy) and ``error_detail`` — instead
        of being indistinguishable from a success; the exception always
        propagates."""
        t0 = time.perf_counter()
        try:
            yield
        except BaseException as e:
            from graphmine_tpu.pipeline.resilience import classify_error

            self.emit(
                phase, seconds=round(time.perf_counter() - t0, 4),
                ok=False, error=classify_error(e), error_detail=repr(e),
                **kv,
            )
            raise
        self.emit(phase, seconds=round(time.perf_counter() - t0, 4), **kv)

    @contextlib.contextmanager
    def span(self, name: str, emit: bool = True, annotate: bool = True,
             remote=None, new_trace: bool = False, **attrs):
        """Open a tracer span for the block (no-op yielding None without
        a tracer). ``emit``: write a ``span`` record at close (the phase
        waterfall's raw material) — superstep spans pass False so a long
        run is not doubled by per-superstep span records (``lpa_iter``
        already carries the superstep span's identity). ``annotate``:
        also enter a ``jax.profiler.TraceAnnotation`` named by the span
        path, so XLA profiler traces line up with the span tree.
        ``remote``/``new_trace`` pass through to
        :meth:`~graphmine_tpu.obs.spans.Tracer.span` — adopt a
        propagated :class:`~graphmine_tpu.obs.spans.TraceContext`, or
        mint a per-request trace (the fleet router's root span)."""
        if self.tracer is None:
            yield None
            return
        _install_compile_listener()
        sp = None
        try:
            with self.tracer.span(
                name, remote=remote, new_trace=new_trace, **attrs
            ) as sp:
                if annotate:
                    with xla_annotation(sp.path):
                        yield sp
                else:
                    yield sp
        finally:
            if emit and sp is not None:
                self.emit(
                    "span", _span=sp, name=sp.name,
                    seconds=round(sp.seconds, 4), status=sp.status,
                    **sp.attrs,
                )

    def span_attrs(self, **attrs) -> None:
        """Set attributes on the span open on this thread (they ride its
        ``span`` record at close); nothing without a tracer, and nothing
        on the root span, which writes no record."""
        if self.tracer is not None:
            sp = self.tracer.current()
            if sp is not self.tracer.root:
                sp.attrs.update(attrs)

    def of_phase(self, phase: str) -> list:
        """All records for one phase name — recovery events (``retry``,
        ``degrade``, ``quarantine``, ``checkpoint_rollback``, ...) are
        phases like any other, so observability tooling and tests filter
        them the same way (span-tagged records filter identically: the
        trace keys ride alongside ``phase``, never replace it)."""
        return [r for r in self.records if r.get("phase") == phase]

    def write_jsonl(self, path: str) -> str:
        """Dump every record as JSON lines (full-file rewrite — the
        explicit export API; run-appending persistence is
        :meth:`finalize`)."""
        with open(path, "w") as f:
            for rec in self.records:
                f.write(json.dumps(rec, default=str) + "\n")
        return path

    def finalize(self, path: str) -> str:
        """End-of-run persistence: when the live stream wrote every
        record, just close it; otherwise (streaming off, or it failed
        mid-run, or a different target path) **append** the records the
        stream never persisted — never truncate, the file may hold prior
        runs' records (a resumed run reusing one ``--metrics-out``)."""
        with _listener_lock:  # closed: no more compile records
            _open_sinks.pop(id(self), None)
        if self._stream is not None:
            try:
                self._stream.close()
            except OSError:
                self._stream_ok = False
            self._stream = None
            if self._stream_ok and self.stream_path == path:
                return path
        # max_records drops shift list positions: the first
        # never-streamed record sits at streamed-minus-dropped (dropped
        # records were, by the emit-order invariant, streamed first).
        start = (
            max(0, self._streamed - self._dropped)
            if path == self.stream_path else 0
        )
        # A stream that died mid-write (ENOSPC, EIO) can leave a torn
        # final line; appending straight after it would merge the torn
        # prefix with the first record below into one unparseable line.
        needs_nl = False
        try:
            with open(path, "rb") as rf:
                rf.seek(-1, os.SEEK_END)
                needs_nl = rf.read(1) != b"\n"
        except (OSError, ValueError):
            pass  # missing or empty file: nothing to repair
        with open(path, "a") as f:
            if needs_nl:
                f.write("\n")
            for rec in self.records[start:]:
                f.write(json.dumps(rec, default=str) + "\n")
        return path

    def tripwire(self, kind: str, shard: int, iteration: int, **kv):
        """Structured record for an in-loop divergence-tripwire firing
        (docs/RESILIENCE.md): which guard, the offending shard index, the
        superstep it fired at — one fixed shape so offline triage can
        filter `of_phase("tripwire")` without per-caller key guessing."""
        return self.emit(
            "tripwire", kind=kind, shard=int(shard),
            iteration=int(iteration), **kv,
        )

    def lpa_iteration(self, it: int, changed: int, num_edges: int, seconds: float, chips: int):
        """Per-superstep record with the headline edges/sec/chip metric."""
        eps = num_edges / seconds if seconds > 0 else float("inf")
        return self.emit(
            "lpa_iter",
            iteration=it,
            labels_changed=changed,
            seconds=round(seconds, 5),
            edges_per_sec=round(eps),
            edges_per_sec_per_chip=round(eps / max(chips, 1)),
        )


def shard_sink(
    obs_dir: str,
    role: str,
    run_id: str | None = None,
    max_records: int | None = None,
) -> MetricsSink:
    """One process's slice of the federated metrics plane (ISSUE 11,
    docs/OBSERVABILITY.md "Fleet tracing"): a streaming sink whose JSONL
    lands at ``<obs_dir>/<role>-<pid>.jsonl``. Every fleet process
    (router, replicas, writer, standby, chaos driver) pointed at one
    ``--obs-dir`` leaves a shard there; ``tools/trace_stitch.py`` joins
    the directory into per-trace cross-process timelines — no log
    aggregator required, the filesystem is the collector."""
    from graphmine_tpu.obs.spans import Tracer

    os.makedirs(obs_dir, exist_ok=True)
    safe_role = "".join(
        c if c.isalnum() or c in "-_" else "-" for c in role
    ) or "proc"
    return MetricsSink(
        stream_path=os.path.join(
            obs_dir, f"{safe_role}-{os.getpid()}.jsonl"
        ),
        tracer=Tracer(run_id=run_id),
        max_records=max_records,
    )


def _reduce_capture(profile_dir: str, sink: MetricsSink) -> dict:
    """Reduce the capture just written (obs/devtrace.py) into
    ``device_scope`` and ``device_idle`` records; returns what the
    ``profile_capture`` record says about it."""
    from graphmine_tpu.obs import devtrace
    from graphmine_tpu.obs.schema import DEVICE_SCOPES

    t0 = time.perf_counter()
    path = devtrace.newest_xplane(profile_dir)
    root = sink.tracer.root.path if sink.tracer is not None else "run"
    reduced = devtrace.reduce_capture(
        *devtrace.read_xplane(path, root), DEVICE_SCOPES
    )
    for row in reduced["scopes"]:
        sink.emit("device_scope", **row)
    for row in reduced["idle"]:
        sink.emit("device_idle", **row)
    return {
        "trace_bytes": os.path.getsize(path),
        "devices": reduced["devices"],
        "busy_seconds": reduced["busy_seconds"],
        "reduce_seconds": round(time.perf_counter() - t0, 4),
    }


@contextlib.contextmanager
def maybe_profile(profile_dir: str | None, sink: MetricsSink | None = None):
    """One jax.profiler capture around the block — ``run_pipeline`` wraps
    the whole run in it, and it works as well around any single call
    (one ``label_propagation(..., sink=sink)``).

    The capture holds the host's annotations and no Python call stacks
    (those slow the host and swell the file). While it is open, the
    persistent compile cache keys on the programs' metadata too: jax
    leaves op names and source lines out of the key by default, so an
    executable that an older checkout put in the cache would be loaded
    with that checkout's names, and the capture would report scopes the
    code no longer has (or none). A program this process already holds
    keeps the names it was compiled with: profile in a fresh process.
    On a clean stop the newest ``.xplane.pb`` is reduced in the process:
    ``device_scope`` records (device seconds by named scope, program and
    program span) and one ``device_idle`` record per chapter, then
    ``profile_capture`` with the file's size and the busy seconds the
    scopes add up to. A capture with no device plane (the CPU backend)
    reduces to no rows.

    Hardened (ISSUE 3 satellite): a failing ``start_trace`` runs the body
    unprofiled instead of aborting the run, and ``stop_trace`` failures
    are contained — a raise out of the ``finally`` would *mask the
    body's own error*, which is the one the operator needs; a failing
    reduction is contained the same way. Every outcome is recorded as a
    ``profile_capture`` record carrying the trace dir, so offline
    reports can link the XLA trace (or its absence) to the run.
    """
    if not profile_dir:
        yield
        return
    import jax

    names_in_key = "jax_compilation_cache_include_metadata_in_key"
    was_in_key = getattr(jax.config, names_in_key)
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    except Exception as e:
        log.warning("profiler start_trace(%s) failed: %r; running "
                    "unprofiled", profile_dir, e)
        if sink is not None:
            sink.emit("profile_capture", dir=profile_dir, ok=False,
                      error=repr(e))
        yield
        return
    jax.config.update(names_in_key, True)
    try:
        yield
    finally:
        jax.config.update(names_in_key, was_in_key)
        t_stop = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("profiler stop_trace failed: %r (trace dir %s may "
                        "be incomplete)", e, profile_dir)
            if sink is not None:
                sink.emit("profile_capture", dir=profile_dir, ok=False,
                          error=repr(e))
        else:
            stop_seconds = time.perf_counter() - t_stop
            if sink is not None:
                try:
                    reduced = _reduce_capture(profile_dir, sink)
                except Exception as e:
                    log.warning("could not reduce the capture under %s: %r",
                                profile_dir, e)
                    reduced = {"reduce_error": repr(e)}
                sink.emit("profile_capture", dir=profile_dir, ok=True,
                          stop_seconds=round(stop_seconds, 4), **reduced)

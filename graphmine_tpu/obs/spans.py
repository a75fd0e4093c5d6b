"""Hierarchical span context: run_id -> phase -> rung -> superstep.

The resilience machine (PRs 1-2) emits every recovery decision as a flat
JSONL record — but with no run, trace, or span identity an operator
cannot reconstruct *which* retry belonged to *which* phase on *which*
mesh rung. A :class:`Tracer` owns one run's identity (``run_id`` +
``trace_id``) and a thread-local stack of open :class:`Span`\\ s; the
:class:`~graphmine_tpu.pipeline.metrics.MetricsSink` stamps every record
with the current span's ids and slash-joined path, so retry / degrade /
mesh_degrade / tripwire / checkpoint records join into one causal
timeline (``tools/obs_report.py``).

Timings are **monotonic** (``time.perf_counter``) — span durations never
go negative under NTP steps; the wall-clock ``start_t`` exists only so
offline reports can align spans with record ``t`` values.

Cross-process propagation (ISSUE 11, docs/OBSERVABILITY.md "Fleet
tracing"): a :class:`TraceContext` is the wire form of one span's
identity — ``to_header()`` renders a ``traceparent``-style header, the
receiving process parses it with :func:`TraceContext.from_header` and
opens its spans with ``remote=ctx``, adopting the sender's ``trace_id``
and parenting under the sender's span. Every record the receiver emits
then lands in the SAME trace, so ``tools/trace_stitch.py`` can join the
per-process JSONL shards of a fleet (router → replicas → writer →
standby) into one causal timeline with no id-mapping table.

Stdlib-only. :func:`xla_annotation` opportunistically enters a
``jax.profiler.TraceAnnotation`` named by the span path — but only when
jax is *already imported*, so host-side tooling that never touches a
device pays nothing.
"""

from __future__ import annotations

import contextlib
import re
import secrets
import sys
import threading
import time
from dataclasses import dataclass, field


def new_run_id() -> str:
    """Sortable-by-start, collision-safe run identity:
    ``YYYYMMDDTHHMMSS-<6 hex>`` (UTC)."""
    return time.strftime("%Y%m%dT%H%M%S", time.gmtime()) + "-" + secrets.token_hex(3)


def _new_id(nbytes: int = 4) -> str:
    return secrets.token_hex(nbytes)


# The header every fleet hop carries (router -> replica, router ->
# writer, probe). traceparent-STYLE: version-trace_id-span_id-flags,
# with this repo's id widths (16-hex trace, 8-hex span) instead of
# W3C's fixed 32/16 — zero-padding to W3C widths and stripping it back
# is a round-trip hazard a single-format fleet doesn't need.
TRACE_HEADER = "traceparent"

# Parsed ids are echoed into response headers and stamped into records:
# constrain them so a hostile header can't smuggle newlines/quotes
# (the serve/server.py request-id discipline).
_HEX_ID_RE = re.compile(r"[0-9a-f]{8,64}")


@dataclass(frozen=True)
class TraceContext:
    """One span's identity on the wire: what a process needs to open a
    child span of a span living in ANOTHER process."""

    trace_id: str
    span_id: str
    sampled: bool = True

    def to_header(self) -> str:
        """``00-<trace_id>-<span_id>-<01|00>``."""
        return (
            f"00-{self.trace_id}-{self.span_id}-"
            f"{'01' if self.sampled else '00'}"
        )

    @classmethod
    def from_header(cls, value) -> "TraceContext | None":
        """Parse a propagated header; ``None`` on anything malformed —
        an unparseable traceparent must degrade to a fresh local trace,
        never crash a request handler."""
        if not isinstance(value, str) or not value:
            return None
        parts = value.strip().lower().split("-")
        if len(parts) != 4:
            return None
        version, trace_id, span_id, flags = parts
        if not re.fullmatch(r"[0-9a-f]{2}", version):
            return None
        if not _HEX_ID_RE.fullmatch(trace_id):
            return None
        if not _HEX_ID_RE.fullmatch(span_id):
            return None
        if len(flags) != 2:
            return None
        return cls(trace_id, span_id, sampled=flags[-1] == "1")


def sink_trace_header(sink) -> str:
    """The calling thread's current span of ``sink``'s tracer, rendered
    as a propagatable ``traceparent`` header — "" when the sink has no
    tracer (tracing off). The one place the sink→header formula lives;
    every fleet process (router forwards, replica WAL stamps, probes)
    propagates through here so the wire format can never fork."""
    tracer = getattr(sink, "tracer", None)
    if tracer is None:
        return ""
    return tracer.current().context().to_header()


@dataclass
class Span:
    """One timed node of the span tree. ``path`` is the slash-joined name
    chain from the root (``run/lpa/rung:ring@4/superstep``) — records
    carry it verbatim so offline triage needs no id-graph walk."""

    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    path: str
    start_t: float                      # wall clock, for report alignment
    start_mono: float                   # perf_counter, for durations
    end_mono: float | None = None
    attrs: dict = field(default_factory=dict)
    status: str = "ok"

    @property
    def seconds(self) -> float:
        """Monotonic duration; an open span reports its age so far."""
        end = self.end_mono if self.end_mono is not None else time.perf_counter()
        return end - self.start_mono

    def context(self) -> TraceContext:
        """This span's wire identity — what :meth:`to_header` of the
        result propagates to the next process."""
        return TraceContext(self.trace_id, self.span_id)


class Tracer:
    """One run's span tree. The root span ("run") opens at construction
    and closes via :meth:`close`; :meth:`span` nests under the current
    thread's innermost open span.

    Thread model: each thread has its own open-span stack; a thread with
    no open span (the heartbeat thread, a watchdog worker) falls back to
    the **root** span, so records emitted there still carry the run and
    trace ids. :meth:`latest` returns the most recently entered open span
    across all threads — what the heartbeat reports as the current phase
    without the emitting thread needing any span of its own.
    """

    def __init__(self, run_id: str | None = None):
        self.run_id = run_id or new_run_id()
        self.trace_id = _new_id(8)
        self._local = threading.local()
        self._lock = threading.Lock()
        now = time.time()
        self.root = Span(
            name="run", trace_id=self.trace_id, span_id=_new_id(),
            parent_id=None, path="run", start_t=now,
            start_mono=time.perf_counter(),
        )
        self._latest: Span = self.root

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> Span:
        """This thread's innermost open span (the root when none)."""
        stack = self._stack()
        return stack[-1] if stack else self.root

    def latest(self) -> Span:
        """Most recently entered open span across all threads."""
        with self._lock:
            return self._latest

    @contextlib.contextmanager
    def span(
        self, name: str, remote: TraceContext | None = None,
        new_trace: bool = False, **attrs,
    ):
        """Open a child span of the current one for the ``with`` block.
        An escaping exception marks ``status="error"`` (and propagates);
        the span always closes with a monotonic end time.

        Cross-process identity (docs/OBSERVABILITY.md "Fleet tracing"):

        - ``remote=ctx`` parents the span under a span living in
          ANOTHER process — it adopts ``ctx.trace_id`` and sets
          ``parent_id`` to the remote span's id, so every record emitted
          inside lands in the propagating process's trace. The path
          restarts at ``name`` (the local path chain belongs to the
          local tree, not the remote one).
        - ``new_trace=True`` mints a fresh ``trace_id`` for the span's
          subtree — the fleet router's root-span-per-request, so each
          request is its OWN trace instead of one run-wide trace.

        Nested spans inherit their parent's ``trace_id`` (not the
        tracer's), so a whole subtree opened under a remote/new-trace
        span stays in that trace.
        """
        if remote is not None and new_trace:
            raise ValueError("span(): remote= and new_trace= are exclusive")
        parent = self.current()
        if remote is not None:
            trace_id, parent_id, path = remote.trace_id, remote.span_id, name
        elif new_trace:
            trace_id, parent_id, path = _new_id(8), None, name
        else:
            trace_id = parent.trace_id
            parent_id = parent.span_id
            path = f"{parent.path}/{name}"
        sp = Span(
            name=name, trace_id=trace_id, span_id=_new_id(),
            parent_id=parent_id, path=path,
            start_t=time.time(), start_mono=time.perf_counter(),
            attrs=dict(attrs),
        )
        stack = self._stack()
        stack.append(sp)
        with self._lock:
            self._latest = sp
        try:
            yield sp
        except BaseException:
            sp.status = "error"
            raise
        finally:
            sp.end_mono = time.perf_counter()
            if stack and stack[-1] is sp:
                stack.pop()
            else:  # defensive: never let a mismatched exit corrupt the stack
                try:
                    stack.remove(sp)
                except ValueError:
                    pass
            with self._lock:
                if self._latest is sp:
                    self._latest = self.current()

    def close(self) -> Span:
        """End the root span (idempotent); returns it for the run record."""
        if self.root.end_mono is None:
            self.root.end_mono = time.perf_counter()
        return self.root


def xla_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named by the span path — the
    bridge that lines XLA profiler traces up with the span tree — or a
    null context when jax is not already imported (a tracer used by
    host-only tooling must not drag the runtime in) or the profiler
    API is unavailable."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


class _Stage:
    """What :func:`stage_span` yields: the two things a stage does to
    its own span, and the span's seconds. Without a sink both do
    nothing, so an ops function called with ``sink=None`` records
    nothing and syncs nothing."""

    def __init__(self, sink=None, span=None):
        self._sink = sink
        self._span = span

    @property
    def seconds(self) -> float | None:
        """The stage's span seconds (its age while open): what a caller
        that also writes a summary record reports, instead of holding a
        second clock beside the span's. ``None`` where there is no span
        (no sink, or a sink without a tracer)."""
        return None if self._span is None else self._span.seconds

    def note(self, **attrs) -> None:
        """Counts that explain the seconds, set on the open span."""
        if self._sink is not None:
            self._sink.span_attrs(**attrs)

    def sync(self, out):
        """Wait for the stage's device outputs (duck-typed
        ``block_until_ready`` on ``out`` or on each element of a tuple
        or list of them) and hand ``out`` back: a stage span closes
        only when its device work is done, or its seconds would be the
        next stage's."""
        if self._sink is not None:
            for leaf in out if isinstance(out, (tuple, list)) else (out,):
                block = getattr(leaf, "block_until_ready", None)
                if block is not None:
                    block()
        return out


_NO_STAGE = _Stage()


@contextlib.contextmanager
def stage_span(sink, name: str, **attrs):
    """A stage inside a chapter, where host and device alternate:
    ``sink.span(name, **attrs)`` (a ``span`` record at close and a
    ``TraceAnnotation`` on the profiler's clock) yielding a
    :class:`_Stage`. ``sink=None`` yields the stage that does nothing."""
    if sink is None:
        yield _NO_STAGE
        return
    with sink.span(name, **attrs) as span:
        yield _Stage(sink, span)

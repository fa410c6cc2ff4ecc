"""Unified query-lifecycle tracing: a hierarchical span tree per query.

Every user-facing action (`DataFrame.collect/count`, `create_index`,
`delete_index`, `vacuum_index`, `explain(analyze=True)`) opens a ROOT span
carrying a stable `query_id`; physical operators, the planner, the optimizer
rules, the action's operation-log writes, and every stage bracket of the
pipelined executors (`profiling.StageTimings.timed`: one span per file
decoded, per bucket written, ...) attach child spans under it — one tree
answering "where did this query's time go and which caches/rules fired".

Design rules:

- **Off by default, zero device impact.** Spans record only while a sink is
  active: ``HYPERSPACE_TRACE_FILE`` set (JSONL export), ``HYPERSPACE_TRACING
  =1``, or a `capture()` scope (what `explain(analyze=True)` uses). When
  inactive every hook degrades to one predicate check and a shared no-op
  span — no allocation, no jax import, no new compilations.
- **Thread-safe child spans.** The ambient parent rides a `contextvars.
  ContextVar` (per-thread under plain threading); pool workers that outlive
  the submitting context pass `parent=` explicitly. Span/trace mutation is
  lock-guarded; a worker that raises inside a `span()` scope closes its span
  with ``status="error"`` before the exception propagates.
- **Bounded.** Finished traces land in a ``deque(maxlen=16)`` (same bound as
  the stage-summary histories); a long-lived session can never grow
  telemetry with query count. Per-trace span count is capped so a runaway
  loop inside one traced query cannot hold unbounded memory either.
- **Device correlation.** While recording, every span also enters a
  `jax.profiler.TraceAnnotation` on the thread that opened it (only when jax
  is already imported — tracing must never pay the import), so a
  `jax.profiler` trace running at the time holds each span in its host plane
  beside the device timeline. `start_s` is read from the clock the profiler
  stamps host events with (`time.time_ns()`, the wall clock); durations come
  from the monotonic clock.

JSONL export (``HYPERSPACE_TRACE_FILE``): one line per span, written when the
root span ends — `{"query_id", "span_id", "parent_id", "name", "start_s",
"duration_s", "status", "attrs"}`. Every span of a trace shares the root's
`query_id`; `parent_id` of the root is null and resolves within the file for
every other span (schema pinned by tests/test_tracing.py and the CI smoke
leg).
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, Iterator, List, Optional

ENV_TRACE_FILE = "HYPERSPACE_TRACE_FILE"
ENV_TRACING = "HYPERSPACE_TRACING"
#: Live Chrome-trace capture directory (`stage_ledger.ENV_TIMELINE_DIR`):
#: every finalized root trace writes one timeline-<query_id>.json here.
ENV_TIMELINE_DIR = "HYPERSPACE_TIMELINE_DIR"

#: Spans per trace hard cap (a traced query touching thousands of operators
#: keeps the tree; further spans are dropped, counted per trace, and surfaced
#: at finalize as the root's `spans_dropped` attr + the
#: `trace.spans.dropped` counter — no silent cap).
MAX_SPANS_PER_TRACE = 4096

_RECENT: "deque[QueryTrace]" = deque(maxlen=16)
_recent_lock = threading.Lock()
_export_lock = threading.Lock()

_current_span: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "hyperspace_current_span", default=None
)
_capture: "contextvars.ContextVar[Optional[Capture]]" = contextvars.ContextVar(
    "hyperspace_trace_capture", default=None
)


def new_query_id() -> str:
    return uuid.uuid4().hex[:16]


class QueryTrace:
    """All spans of one root query, in creation order (root first)."""

    def __init__(self, query_id: str):
        self.query_id = query_id
        self.spans: List[Span] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._next_id = 0

    def _register(self, span: "Span") -> bool:
        with self._lock:
            if len(self.spans) >= MAX_SPANS_PER_TRACE:
                self.dropped += 1
                return False
            span.span_id = self._next_id
            self._next_id += 1
            self.spans.append(span)
            return True

    @property
    def root(self) -> "Span":
        return self.spans[0]

    def spans_by_parent(self) -> Dict[Optional[int], List["Span"]]:
        out: Dict[Optional[int], List[Span]] = {}
        with self._lock:
            for s in self.spans:
                out.setdefault(s.parent_id, []).append(s)
        return out

    def find(self, name: str) -> List["Span"]:
        with self._lock:
            return [s for s in self.spans if s.name == name]


class Span:
    """One named, timed node of a query's span tree."""

    __slots__ = (
        "trace",
        "name",
        "span_id",
        "parent_id",
        "start_s",
        "_t0",
        "duration_s",
        "status",
        "attrs",
        "_lock",
        "_registered",
        "_ann",
    )

    #: Whether this span records (the shared no-op span says False): lets a
    #: site skip computing an attribute nobody will read.
    recording = True

    def __init__(self, trace: QueryTrace, name: str, parent_id: Optional[int], attrs=None):
        self.trace = trace
        self.name = name
        self.span_id = -1
        self.parent_id = parent_id
        self.start_s = time.time_ns() / 1e9
        self._t0 = time.monotonic()
        self.duration_s: Optional[float] = None
        self.status = "ok"
        self.attrs: dict = dict(attrs) if attrs else {}
        self._lock = threading.Lock()
        self._registered = trace._register(self)
        self._ann = _annotation(name)

    @property
    def query_id(self) -> str:
        return self.trace.query_id

    def set_attr(self, key: str, value) -> None:
        with self._lock:
            self.attrs[key] = value

    def add_attrs(self, **attrs) -> None:
        with self._lock:
            self.attrs.update(attrs)

    def append_attr(self, key: str, value) -> None:
        """Append to a list-valued attribute (rule decisions accumulate)."""
        with self._lock:
            self.attrs.setdefault(key, []).append(value)

    def inc_attr(self, key: str, delta) -> None:
        """Accumulate a numeric attribute atomically (compile-observatory
        deltas: several compiles may land on one operator span)."""
        with self._lock:
            self.attrs[key] = self.attrs.get(key, 0) + delta

    def end(self, status: Optional[str] = None, error: Optional[BaseException] = None) -> None:
        # Locked end-to-end: the exporter's end(status="unclosed") on a
        # worker span that outlived the root must not interleave with the
        # worker's own end(error=...) — the first end wins atomically.
        with self._lock:
            if self.duration_s is not None:
                return  # idempotent: the first end wins
            self.duration_s = max(0.0, time.monotonic() - self._t0)
            if error is not None:
                self.status = "error"
                self.attrs["error"] = f"{type(error).__name__}: {error}"
            elif status is not None:
                self.status = status
            ann, self._ann = self._ann, None
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass

    def to_json(self) -> dict:
        # Attrs snapshot under the span lock: a still-running worker span
        # mutating attrs during export would otherwise raise mid-serialize
        # (and _finalize's swallow would drop the whole trace's lines).
        with self._lock:
            attrs = dict(self.attrs)
            duration = self.duration_s
            status = self.status
        return {
            "query_id": self.trace.query_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_s": round(self.start_s, 6),
            "duration_s": None if duration is None else round(duration, 6),
            "status": status,
            "attrs": attrs,
        }


class _NoopSpan:
    """Shared do-nothing span: what every hook gets while tracing is off."""

    __slots__ = ()
    name = "<noop>"
    span_id = -1
    parent_id = None
    query_id = ""
    duration_s = 0.0
    status = "ok"
    attrs: dict = {}
    recording = False

    def set_attr(self, key, value):
        pass

    def add_attrs(self, **attrs):
        pass

    def append_attr(self, key, value):
        pass

    def inc_attr(self, key, delta):
        pass

    def end(self, status=None, error=None):
        pass


NOOP_SPAN = _NoopSpan()


class Capture:
    """In-memory sink for one traced execution (`explain(analyze=True)` and
    tests): the next root trace FINISHED on this context lands in `.trace`."""

    def __init__(self):
        self.trace: Optional[QueryTrace] = None


def active() -> bool:
    """Whether spans should record: any sink is attached. One env lookup on
    the hot path; everything heavier happens only when this is True."""
    if _capture.get() is not None:
        return True
    if os.environ.get(ENV_TRACE_FILE):
        return True
    if os.environ.get(ENV_TIMELINE_DIR):
        # Live timeline capture is a sink: spans must record for _finalize
        # to have a tree to convert.
        return True
    return os.environ.get(ENV_TRACING) == "1"


def current_span():
    return _current_span.get()


def set_attr(key: str, value) -> None:
    """Attribute on the ambient span; no-op without one (or tracing off)."""
    sp = _current_span.get()
    if sp is not None:
        sp.set_attr(key, value)


@contextlib.contextmanager
def capture() -> Iterator[Capture]:
    """Force-record the traces started under this scope and hand the first
    finished root trace to the caller (independent of the env sinks)."""
    cap = Capture()
    token = _capture.set(cap)
    try:
        yield cap
    finally:
        _capture.reset(token)


def _annotation(name: str):
    """`jax.profiler.TraceAnnotation` when jax is ALREADY imported (tracing
    must never trigger the import), else None. Setup failures are absorbed —
    the host span still records."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann
    except Exception:
        return None


@contextlib.contextmanager
def query_span(name: str, **attrs) -> Iterator:
    """Root span of one user-facing action (collect/count/build/explain).

    Nested under an already-active span (e.g. a scalar subquery's inner
    collect inside the outer query) it degrades to a plain child span — ONE
    query_id per outermost action. When no sink is active it yields the
    shared no-op span.

    The per-query resource ledger (`telemetry.accounting`) shares this exact
    boundary: a root span carries a ledger; with spans off but accounting on
    (the continuous exporter, or ``HYPERSPACE_ACCOUNTING=1``) a ledger-only
    scope opens around the no-op span, so resource attribution and latency
    histograms survive without paying for span trees."""
    from . import accounting as _accounting

    if not active():
        if not _accounting.enabled():
            yield NOOP_SPAN
            return
        with _accounting.ledger_scope(new_query_id(), name):
            yield NOOP_SPAN
        return
    parent = _current_span.get()
    if parent is not None:
        with span(name, **attrs) as sp:
            yield sp
        return
    trace = QueryTrace(new_query_id())
    root = Span(trace, name, None, attrs)
    # Tenant label end to end: the ambient tenant (the serving layer's
    # `tenant_scope`) rides the root span like it rides the ledger, so the
    # JSONL trace and explain(analyze) attribute the query to its tenant.
    tenant = _accounting.current_tenant()
    if tenant is not None:
        root.set_attr("tenant", tenant)
    token = _current_span.set(root)
    led = _accounting.ledger_scope(trace.query_id, name, root=root)
    led.__enter__()
    try:
        yield root
        root.end()
    except BaseException as e:
        root.end(error=e)
        raise
    finally:
        # Ledger closes AFTER root.end (it reads the root's duration and
        # writes the `ledger` attr) and BEFORE _finalize (the JSONL export
        # must carry the closed ledger).
        try:
            led.__exit__(None, None, None)
        except Exception:
            pass
        _current_span.reset(token)
        _finalize(trace)


def start_span(name: str, parent=None, **attrs):
    """Open a child span under `parent` (default: the ambient span) that the
    caller ends itself (`Span.end`), for a scope that is not one lexical
    block — e.g. a `StageTimings` that opens its ``<kind>:stages`` span at
    construction and closes it when the summary is taken. The span does NOT
    become the ambient span. Without an ambient root (or with tracing off)
    it returns the shared no-op span.

    An EXPLICIT real parent records regardless of this thread's `active()`
    view: pool workers run in a fresh contextvars context, so the submitting
    code passing `parent=` is the proof a sink is attached — without this, a
    worker's span would silently no-op (found by the pool hammer test)."""
    if parent is None:
        if not active():
            return NOOP_SPAN
        parent = _current_span.get()
    if parent is None or isinstance(parent, _NoopSpan):
        return NOOP_SPAN
    return Span(parent.trace, name, parent.span_id, attrs)


@contextlib.contextmanager
def span(name: str, parent=None, **attrs) -> Iterator:
    """Child span under `parent` (default: the ambient span), ambient for the
    body (`start_span` decides whether it records). Exceptions close the span
    with ``status="error"`` and propagate."""
    sp = start_span(name, parent, **attrs)
    if sp is NOOP_SPAN:
        yield sp
        return
    token = _current_span.set(sp)
    try:
        yield sp
        sp.end()
    except BaseException as e:
        sp.end(error=e)
        raise
    finally:
        _current_span.reset(token)


def recent_traces() -> List[QueryTrace]:
    """Finished root traces, oldest first (bounded history, newest last)."""
    with _recent_lock:
        return list(_RECENT)


def last_trace() -> Optional[QueryTrace]:
    with _recent_lock:
        return _RECENT[-1] if _RECENT else None


def _finalize(trace: QueryTrace) -> None:
    """Root ended: bank the trace, hand it to a same-context capture, and
    export JSONL when the env sink is set. Export failures are swallowed —
    telemetry must never fail the query it observed."""
    if trace.dropped:
        # No silent caps: the span-cap overflow rides the root (JSONL +
        # explain consumers see it) and the process-wide counter.
        trace.root.set_attr("spans_dropped", trace.dropped)
        from . import metrics as _metrics

        _metrics.counter("trace.spans.dropped").inc(trace.dropped)
    with _recent_lock:
        _RECENT.append(trace)
    cap = _capture.get()
    if cap is not None and cap.trace is None:
        cap.trace = trace
    path = os.environ.get(ENV_TRACE_FILE)
    if path:
        try:
            lines = []
            for s in list(trace.spans):
                if s.duration_s is None:
                    # A worker span left open (its pool outlived the root):
                    # export it closed at the root's end with an explicit
                    # marker rather than an unparseable null duration.
                    s.end(status="unclosed")
                lines.append(json.dumps(s.to_json(), default=str))
            from . import rotation as _rotation

            with _export_lock:
                # Size-capped rotation (HYPERSPACE_TRACE_MAX_MB; off by
                # default): one whole trace per append, so rotated files each
                # stay independently parseable.
                _rotation.append(
                    path, "\n".join(lines) + "\n", _rotation.ENV_TRACE_MAX_MB
                )
        except Exception:
            pass
    # Live timeline capture: with HYPERSPACE_TIMELINE_DIR set, every root
    # trace also lands as one Chrome-trace/Perfetto JSON file (one lane per
    # stage / worker family / op class — `stage_ledger.chrome_trace`), so a
    # causal query timeline needs no post-hoc tool run. One env read off.
    tdir = os.environ.get(ENV_TIMELINE_DIR)
    if tdir:
        try:
            from . import stage_ledger as _stage_ledger

            spans = []
            for s in list(trace.spans):
                if s.duration_s is None:
                    s.end(status="unclosed")
                spans.append(s.to_json())
            doc = _stage_ledger.chrome_trace(spans)
            os.makedirs(tdir, exist_ok=True)
            out = os.path.join(tdir, f"timeline-{trace.query_id}.json")
            with open(out, "w") as fh:
                json.dump(doc, fh, default=str)
        except Exception:
            pass

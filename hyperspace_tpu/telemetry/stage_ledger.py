"""Per-query stage ledger: cost vectors attributed to the pipeline stage
that spent them.

The four existing sinks each answer one question in isolation — stage spans
(PR 4) know WHEN a stage ran, the query ledger (PR 6) knows WHAT the query
spent, the device observatory (PR 14) knows what the DEVICE did, and the
planner outcome store (PR 17) knows WHICH arm ran. None of them joins cost
to stage, so a pushdown win can hide behind a cold decode and a packed-codes
regression behind a warm cache (whole-wall A/B misattribution — ROADMAP
item 2's named frontier). This module is the join key:

- **`stage_scope(name)`** — a contextvar marking the CURRENT stage. Every
  `StageTimings.timed(stage)` block composes it automatically, so the
  streamed executors' pad/probe/expand/verify/gather/eval/decode/filter/
  partial/merge brackets label themselves; dedicated sites label ``h2d``
  (device_cache uploads) and ``exchange`` (the mesh all-to-all). Exiting the
  scope banks the stage's wall seconds on the ambient `resilience.QueryScope`
  — busy time across workers, like `StageTimings` (stages overlap; walls are
  NOT a wall-clock partition).
- **Counter stamping** — `accounting.add` forwards every cost-vector counter
  (`_COUNTER_VECTOR`) through `stamp_counter`, billing it to the ambient
  stage (or the literal ``<unlabeled>`` bucket, so stage totals reconcile
  with the whole-query counters BY CONSTRUCTION). Pool workers inherit the
  submitting stage: the submit sites capture `worker_stage()` next to the
  existing `use_ledger`/`use_scope` adoption.
- **`close_stages()`** — the root-ledger-close join: per-stage cost vectors
  ``{wall_s, device_s, bytes_decoded, bytes_h2d, bytes_padded, xla_compiles,
  rows}`` attached as the ledger's ``stages`` key, from where history
  baselines, hsreport's stage-drift table, `explain(analyze=True)`'s
  Attribution section, and the exporter all read it.

The stage WALLS live on the `QueryScope` (not the `QueryLedger`): the
adaptive planner's stage-grain learning (`plananalysis/attribution.py`)
must work with every telemetry sink off, and the scope is the one object
every worker thread already adopts. Counter stamps ride the same ledger —
they only exist when a `QueryLedger` is live anyway.

Zero-cost-off: ``HYPERSPACE_STAGE_ATTRIBUTION=0`` makes `stage_scope` one
env read and the stamp sites one flag test (the bool is captured once per
ledger open); results are byte-identical in both states (this module only
observes). Pinned by tests/test_stage_attribution.py's counting oracle.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from typing import Dict, Iterator, List, Optional

ENV_STAGE_ATTRIBUTION = "HYPERSPACE_STAGE_ATTRIBUTION"

#: The bucket counters land in when no stage is ambient — kept visible (not
#: dropped) so per-stage totals always sum to the whole-query counters.
UNLABELED = "<unlabeled>"

#: Query-ledger counter key -> stage cost-vector field. Counters outside
#: this map are whole-query-only (stage attribution does not claim them).
_COUNTER_VECTOR = {
    "device_time_s": "device_s",
    "bytes_decoded": "bytes_decoded",
    "device_upload_bytes": "bytes_h2d",
    "pad_bytes_padded": "bytes_padded",
    "xla_compiles": "xla_compiles",
}

#: Canonical cost-vector field order (rendering + docs).
VECTOR_FIELDS = (
    "wall_s",
    "device_s",
    "bytes_decoded",
    "bytes_h2d",
    "bytes_padded",
    "xla_compiles",
    "rows",
)

_stage: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "hyperspace_stage", default=None
)


def enabled() -> bool:
    """Default ON; ``HYPERSPACE_STAGE_ATTRIBUTION=0`` restores the four
    disjoint sinks with one env read per stage bracket."""
    return os.environ.get(ENV_STAGE_ATTRIBUTION, "") != "0"


class StageLedger:
    """Thread-safe per-stage accumulator for one root query scope."""

    __slots__ = ("_lock", "_stages")

    def __init__(self):
        self._lock = threading.Lock()
        self._stages: Dict[str, Dict[str, float]] = {}

    def add(self, stage: str, field: str, n) -> None:
        with self._lock:
            vec = self._stages.get(stage)
            if vec is None:
                vec = self._stages[stage] = {}
            vec[field] = vec.get(field, 0) + n

    def wall_snapshot(self) -> Dict[str, float]:
        """Per-stage busy wall seconds (what the planner's stage-grain
        observe folds — available with every telemetry sink off)."""
        with self._lock:
            return {
                st: float(vec["wall_s"])
                for st, vec in self._stages.items()
                if vec.get("wall_s")
            }

    def snapshot(self) -> Dict[str, dict]:
        """Per-stage cost vectors, canonical field order, zeros dropped,
        floats rounded — the ledger's ``stages`` key at close."""
        with self._lock:
            out: Dict[str, dict] = {}
            for st in sorted(self._stages):
                vec = self._stages[st]
                row = {}
                for f in VECTOR_FIELDS:
                    v = vec.get(f)
                    if not v:
                        continue
                    row[f] = round(v, 6) if isinstance(v, float) else v
                if row:
                    out[st] = row
            return out


# Lazy resilience handle: resilience imports telemetry.accounting at module
# load and accounting imports this module, so the reverse edge must resolve
# at call time (by which point resilience is always fully imported — a scope
# only exists because resilience.query_scope opened it).
_resilience = None


def _scope_ledger(create: bool) -> Optional[StageLedger]:
    global _resilience
    if _resilience is None:
        from .. import resilience as _r

        _resilience = _r
    sc = _resilience.current_scope()
    if sc is None:
        return None
    sl = sc.stage_ledger
    if sl is None and create:
        with sc._lock:
            sl = sc.stage_ledger
            if sl is None:
                sl = sc.stage_ledger = StageLedger()
    return sl


def current_stage() -> Optional[str]:
    """The ambient stage name (None outside every stage bracket — and always
    None with attribution off, since only `stage_scope` sets it)."""
    return _stage.get()


def worker_stage(default: Optional[str] = None) -> Optional[str]:
    """The stage a pool submit site should bill its workers to: the ambient
    stage when one is set, else `default` (the pool's own stage — e.g. the
    decode pool IS the decode stage) when attribution is on, else None (the
    worker wrapper becomes a no-op)."""
    st = _stage.get()
    if st is not None:
        return st
    if default is not None and enabled():
        return default
    return None


@contextlib.contextmanager
def stage_scope(name: Optional[str]) -> Iterator[None]:
    """Mark the body as stage `name`: counters added inside bill the stage,
    and the body's wall seconds bank on the ambient QueryScope's stage
    ledger at exit. `None` (or attribution off) is a fast no-op. Nested
    scopes re-label (innermost wins) — each level still banks its own wall,
    so nesting the same name would double-bill; sites use distinct names."""
    if name is None or not enabled():
        yield
        return
    token = _stage.set(name)
    t0 = time.monotonic()
    try:
        yield
    finally:
        _stage.reset(token)
        sl = _scope_ledger(create=True)
        if sl is not None:
            sl.add(name, "wall_s", time.monotonic() - t0)


def stamp_counter(key: str, n) -> None:
    """Bill one query-ledger counter to the ambient stage (called by
    `accounting.add` only when the ledger opened with attribution on).
    Counters outside the cost vector return on the dict miss."""
    field = _COUNTER_VECTOR.get(key)
    if field is None:
        return
    sl = _scope_ledger(create=True)
    if sl is None:
        return
    sl.add(_stage.get() or UNLABELED, field, n)


def note_rows(n: int) -> None:
    """Stage-local row throughput (the `rows` vector component). Only stamps
    inside a stage bracket — one contextvar read otherwise."""
    st = _stage.get()
    if st is None or not n:
        return
    sl = _scope_ledger(create=True)
    if sl is not None:
        sl.add(st, "rows", int(n))


def query_stage_walls() -> Optional[Dict[str, float]]:
    """The ambient query's per-stage busy walls so far, or None (attribution
    off / no scope / nothing labeled yet). What the session passes to
    `planner.observe(stages=...)`."""
    if not enabled():
        return None
    sl = _scope_ledger(create=False)
    if sl is None:
        return None
    walls = sl.wall_snapshot()
    return walls or None


def close_stages(led) -> Optional[Dict[str, dict]]:
    """The ledger-close join: the ambient scope's per-stage cost vectors,
    or None when attribution was off for this ledger or nothing accumulated.
    Called by `accounting.ledger_scope` before the ledger snapshots."""
    if not getattr(led, "stage_attr", False):
        return None
    sl = _scope_ledger(create=False)
    if sl is None:
        return None
    snap = sl.snapshot()
    return snap or None


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto conversion (tools/hstimeline.py + the live
# HYPERSPACE_TIMELINE_DIR capture in tracing._finalize share this)
# ---------------------------------------------------------------------------

ENV_TIMELINE_DIR = "HYPERSPACE_TIMELINE_DIR"

#: Span-name prefixes whose spans are stage lanes: every
#: `StageTimings.timed` bracket is one ``<kind>:<stage>`` span under its
#: ``<kind>:stages`` span.
_STAGE_KINDS = ("build", "query", "join")


def _lane_of(span: dict) -> str:
    """Timeline lane for one exported span dict: the root query gets its own
    lane, stage spans get one lane PER STAGE (the causal timeline), operator
    spans share an ``ops`` lane, pool worker spans a ``workers`` lane,
    everything else groups by name family."""
    name = str(span.get("name", ""))
    if span.get("parent_id") is None:
        return "query"
    if ":" in name:
        kind, rest = name.split(":", 1)
        if kind in _STAGE_KINDS:
            if rest == "stages":
                return f"stages:{kind}"
            return f"stage:{rest}"
        if kind == "op":
            return "ops"
        if kind in ("worker", "pool", "decode"):
            return "workers"
        return kind
    return name


def chrome_trace(spans: List[dict]) -> dict:
    """Convert one query's exported span dicts (the `Span.to_json` schema:
    query_id/span_id/parent_id/name/start_s/duration_s/status/attrs) into
    Chrome-trace JSON (``chrome://tracing`` / Perfetto's legacy importer):
    one complete-event (``ph:"X"``) per span, one lane (tid) per stage /
    worker family / op class, thread-name metadata naming the lanes. Spans
    of one stage that overlap in time (eight files decoding at once) go to
    numbered sub-lanes of their stage's lane (``stage:decode#2``, ...), so no
    lane holds two events that partly overlap."""
    spans = [s for s in spans if isinstance(s, dict)]
    timed = sorted(
        (s for s in spans if isinstance(s.get("start_s"), (int, float))),
        key=lambda s: float(s["start_s"]),
    )
    t0 = float(timed[0]["start_s"]) if timed else 0.0
    lanes: Dict[str, int] = {}
    events: List[dict] = []
    stage_ends: Dict[str, List[float]] = {}  # stage lane -> each sub-lane's end
    for s in timed:
        start = float(s["start_s"])
        dur = s.get("duration_s")
        dur = float(dur) if isinstance(dur, (int, float)) else 0.0
        lane = _lane_of(s)
        if lane.startswith("stage:"):
            ends = stage_ends.setdefault(lane, [])
            free = next((i for i, e in enumerate(ends) if e <= start), len(ends))
            if free == len(ends):
                ends.append(0.0)
            ends[free] = start + max(0.0, dur)
            if free:
                lane = f"{lane}#{free + 1}"
        tid = lanes.setdefault(lane, len(lanes) + 1)
        ev = {
            "name": str(s.get("name", "?")),
            "ph": "X",
            "ts": round((start - t0) * 1e6, 1),
            "dur": round(max(0.0, dur) * 1e6, 1),
            "pid": 1,
            "tid": tid,
        }
        attrs = s.get("attrs")
        if isinstance(attrs, dict) and attrs:
            ev["args"] = attrs
        if s.get("status") not in (None, "ok"):
            ev.setdefault("args", {})["status"] = s["status"]
        events.append(ev)
    meta = [
        {
            "name": "thread_name",
            "ph": "M",
            "pid": 1,
            "tid": tid,
            "args": {"name": lane},
        }
        for lane, tid in lanes.items()
    ]
    qids = {s.get("query_id") for s in spans if s.get("query_id")}
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": {
            "query_id": sorted(qids)[0] if qids else None,
            "lanes": sorted(lanes),
        },
    }

"""XLA profiler hooks (SURVEY §5: the reference's only tracing surface is the
explain subsystem; the TPU-native framework additionally exposes the device-level
profiler so "where did the time go" is answerable below the plan level).

`trace(log_dir)` wraps a scope in `jax.profiler` start/stop — the output is an
xprof/TensorBoard trace directory with per-kernel device timelines. `annotate`
names a region so engine phases (probe, exchange, build) are findable in the
trace. Both degrade to no-ops when profiling is unavailable (e.g. a backend
without profiler support), so they are safe to leave in production paths.

The bench consumes this via `BENCH_PROFILE_DIR=/path python bench.py`, which
traces the device section; `block_until_ready` wall deltas in the bench JSON
remain the machine-readable summary (device_time_s / utilization).

`StageTimings` + `record_build_stages` are the HOST-side counterpart for the
pipelined index build: per-stage busy time (decode/hash/h2d/sort/take/write),
wall-clock, and the overlap ratio of each build, surfaced through bench.py's
`bench_detail.build_stages` (see docs/build-pipeline.md). Its stage bracket
is also the build's span tree: one ``<kind>:stages`` span over the
operation's wall and one ``<kind>:<stage>`` span per bracket."""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, Iterator, Optional

from . import stage_ledger
from . import tracing


class StageTimings:
    """Thread-safe per-stage wall-clock accumulator for a pipelined operation
    of one `kind` (``build``, ``query`` or ``join``).

    Stages run CONCURRENTLY (that is the point of the pipeline), so per-stage
    sums measure busy time across workers, not a wall-clock partition:
    `overlap_ratio = sum(stage_s) / wall_s` > 1 means stages genuinely ran on
    top of each other, ~1 means the pipeline degenerated to a serial chain.
    A bracket opened inside another on the same thread (the mesh build's
    steps inside its ``sort``) adds to its own stage's sum but not again to
    the busy time the ratio counts.

    While tracing records, construction opens the ``<kind>:stages`` span under
    the ambient span; `summary()` closes it and hangs the summary on it, and
    leaving the ``with`` block by an exception closes it with
    ``status="error"``, and leaving it without a summary (the operation
    returned before it ran, and its caller falls back to another path) with
    ``status="fallback"``. Use it as a context manager around the
    operation."""

    def __init__(self, kind: str, mode: str = ""):
        self._lock = threading.Lock()
        self._stages: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._nested_s = 0.0  # seconds of brackets inside another, same thread
        self._depth = threading.local()
        self.kind = kind
        self.mode = mode
        self._t0 = time.monotonic()
        self._wall: Optional[float] = None
        self.span = tracing.start_span(f"{kind}:stages")

    def __enter__(self) -> "StageTimings":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # A no-op after summary(): the first end wins.
        self.span.end(status="fallback", error=exc)

    def add(self, stage: str, seconds: float, nested: bool = False) -> None:
        with self._lock:
            self._stages[stage] = self._stages.get(stage, 0.0) + float(seconds)
            self._counts[stage] = self._counts.get(stage, 0) + 1
            if nested:
                self._nested_s += float(seconds)

    @contextlib.contextmanager
    def timed(self, stage: str, **attrs) -> Iterator:
        """THE stage bracket: the body's seconds add to the stage's busy sum,
        it is a stage-attribution scope (telemetry/stage_ledger.py: counters
        ticked inside bill this stage and the busy wall banks on the ambient
        QueryScope), and it is one ``<kind>:<stage>`` span under the stages
        span — on whichever thread runs it, hence the explicit parent. Yields
        that span (the shared no-op span while tracing is off) so the site
        can record the bracket's counts on it; `attrs` start it."""
        depth = getattr(self._depth, "n", 0)
        self._depth.n = depth + 1
        t0 = time.monotonic()
        try:
            with tracing.span(
                f"{self.kind}:{stage}", parent=self.span, **attrs
            ) as sp, stage_ledger.stage_scope(stage):
                yield sp
        finally:
            self._depth.n = depth
            self.add(stage, time.monotonic() - t0, nested=depth > 0)

    @staticmethod
    def timed_or_not(stages: Optional["StageTimings"], stage: str, **attrs):
        """`stages.timed(stage, **attrs)`, or, for a caller that runs outside
        any operation's stages (`stages` None), a bracket that times and
        records nothing and yields the no-op span."""
        if stages is None:
            return contextlib.nullcontext(tracing.NOOP_SPAN)
        return stages.timed(stage, **attrs)

    def finish(self) -> None:
        if self._wall is None:
            self._wall = time.monotonic() - self._t0

    def summary(self, **extra) -> dict:
        """The operation's summary, plus the caller's `extra` counts; closes
        the stages span, which carries the summary as its attributes."""
        self.finish()
        with self._lock:
            wall = self._wall or 0.0
            busy = sum(self._stages.values()) - self._nested_s
            out = {f"{k}_s": round(v, 4) for k, v in sorted(self._stages.items())}
            out["wall_s"] = round(wall, 4)
            out["overlap_ratio"] = round(busy / wall, 3) if wall > 0 else None
            out["mode"] = self.mode
            out["stage_counts"] = dict(sorted(self._counts.items()))
        out.update(extra)
        self.span.add_attrs(**out)
        self.span.end()
        return out


# Most recent index-build / streaming-query / streamed-join stage summaries
# (newest last), consumed by bench.py's bench_detail. Bounded: telemetry must
# never grow with the number of builds/queries a long-lived session performs.
_BUILD_STAGES: "deque[dict]" = deque(maxlen=16)
_QUERY_STAGES: "deque[dict]" = deque(maxlen=16)
_JOIN_STAGES: "deque[dict]" = deque(maxlen=16)
_build_stages_lock = threading.Lock()


def _observe_stage_histograms(kind: str, summary: dict) -> None:
    """Feed one stage summary into the registry's quantile histograms:
    ``latency.<kind>.wall`` for the operation wall clock and
    ``latency.stage.<kind>.<stage>`` per stage's busy seconds — so
    `metrics.snapshot()` carries p50/p90/p99 latency DISTRIBUTIONS across
    operations, not just each operation's last summary. Always on, like the
    counters: a handful of locked observes per operation."""
    from . import metrics as _metrics

    wall = summary.get("wall_s")
    if isinstance(wall, (int, float)):
        _metrics.histogram(f"latency.{kind}.wall").observe(wall)
    for key, val in summary.items():
        if key.endswith("_s") and key != "wall_s" and isinstance(val, (int, float)):
            _metrics.histogram(f"latency.stage.{kind}.{key[:-2]}").observe(val)


def record_build_stages(summary: dict) -> None:
    """Record one build's stage summary (from `StageTimings.summary()`)."""
    d = dict(summary)
    with _build_stages_lock:
        _BUILD_STAGES.append(d)
    _observe_stage_histograms("build", d)


def last_build_stages() -> Optional[dict]:
    """The most recent build's stage summary (None if no build ran yet)."""
    with _build_stages_lock:
        return dict(_BUILD_STAGES[-1]) if _BUILD_STAGES else None


def build_stages_history() -> list:
    """Stage summaries of the last few builds, oldest first."""
    with _build_stages_lock:
        return [dict(d) for d in _BUILD_STAGES]


def record_query_stages(summary: dict) -> None:
    """Per-stage timings of one streaming query execution (decode/filter/
    partial/merge busy time + wall + overlap ratio) — the read-side twin of
    `record_build_stages`, surfaced through bench.py's
    ``bench_detail.query_stages``."""
    d = dict(summary)
    with _build_stages_lock:
        _QUERY_STAGES.append(d)
    _observe_stage_histograms("query", d)


def last_query_stages() -> Optional[dict]:
    """The most recent streaming query's stage summary (None if none ran)."""
    with _build_stages_lock:
        return dict(_QUERY_STAGES[-1]) if _QUERY_STAGES else None


def query_stages_history() -> list:
    """Stage summaries of the last few streaming queries, oldest first."""
    with _build_stages_lock:
        return [dict(d) for d in _QUERY_STAGES]


def record_join_stages(summary: dict) -> None:
    """Per-stage timings of one streamed join→aggregate execution (pad/probe/
    expand/verify/gather/eval/partial busy time + wall + overlap ratio, plus
    class/outlier counts) — surfaced through bench.py's
    ``bench_detail.join_stages``."""
    d = dict(summary)
    with _build_stages_lock:
        _JOIN_STAGES.append(d)
    _observe_stage_histograms("join", d)


def last_join_stages() -> Optional[dict]:
    """The most recent streamed join's stage summary (None if none ran)."""
    with _build_stages_lock:
        return dict(_JOIN_STAGES[-1]) if _JOIN_STAGES else None


def join_stages_history() -> list:
    """Stage summaries of the last few streamed joins, oldest first."""
    with _build_stages_lock:
        return [dict(d) for d in _JOIN_STAGES]


def io_pruning_summary() -> dict:
    """Session-cumulative scan-pushdown counters: row groups scanned vs
    skipped by zone-map pruning, the byte totals behind them, and footer-
    cache traffic. Consumed by ``bench_detail.io_pruning`` so the pruning win
    is measured, not modeled."""
    from . import metrics as _metrics

    return {
        "row_groups_scanned": _metrics.counter("io.pruning.row_groups_scanned").value,
        "row_groups_skipped": _metrics.counter("io.pruning.row_groups_skipped").value,
        "bytes_decoded": _metrics.counter("io.pruning.bytes_decoded").value,
        "bytes_skipped": _metrics.counter("io.pruning.bytes_skipped").value,
        # Encoded-execution byte split: kept-as-codes vs flattened-to-values
        # (engine/encoding.py) — distinguishes what `bytes_decoded` cannot,
        # so effective GB/s is computed over bytes actually moved.
        "bytes_encoded_kept": _metrics.counter("io.pruning.bytes_encoded_kept").value,
        "bytes_materialized": _metrics.counter("io.pruning.bytes_materialized").value,
        "columns_encoded": _metrics.counter("io.encoded.columns_encoded").value,
        "columns_flattened": _metrics.counter("io.encoded.columns_flattened").value,
        "footer_hits": _metrics.counter("io.footer.hits").value,
        "footer_misses": _metrics.counter("io.footer.misses").value,
    }


@contextlib.contextmanager
def trace(log_dir: Optional[str], enabled: bool = True) -> Iterator[None]:
    """Profile a scope into `log_dir` (xprof format); no-op when disabled/unset."""
    if not enabled or not log_dir:
        yield
        return
    import jax

    try:
        jax.profiler.start_trace(log_dir)
    except Exception:
        yield  # profiler unavailable on this backend — scope still runs
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass


@contextlib.contextmanager
def annotate(name: str, enabled: bool = True) -> Iterator[None]:
    """Name a region in the device trace (`jax.profiler.TraceAnnotation`).

    The try covers only annotation SETUP — the body's own exceptions must
    propagate unmasked (a second yield in an except handler would swallow them
    into contextlib's 'generator didn't stop' RuntimeError)."""
    ann = None
    if enabled:
        try:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:
            ann = None
    try:
        yield
    finally:
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass

"""XLA compile observatory: who is compiling, what, how often, for how long.

The r05 TPU bench died inside a 2400 s compile of `ops/hashing.bucket_id`
with ZERO telemetry — no record of which program was compiling, how many
distinct shapes it had already compiled, or how long each took. This module
makes that failure mode diagnosable:

- A ``jax.monitoring`` duration listener (`install`) observes every backend
  compile and jaxpr trace the process performs, feeding the registry:
  ``xla.compiles.count`` / ``xla.compiles.seconds`` (a quantile histogram) /
  ``xla.compiles.traces``, plus ``xla.compile_cache.*`` counters from the
  persistent-cache events. Listener cost is zero between compiles — jax only
  calls it when a compile actually happens.
- Per-program attribution: the engine's jit entry points in ``ops/`` (and
  the fused device helpers) are declared through `observed_jit`, a drop-in
  `jax.jit` wrapper that pushes its label onto a thread-local stack for the
  duration of each call. Compiles are synchronous inside the call, so the
  listener reads the top of that stack — compile count, elapsed seconds, and
  distinct traced shapes per LABEL (`program_summary`), at the cost of one
  list push/pop per jit call.
- Operator-span deltas: while a span is recording, each backend compile also
  increments ``xla_compiles`` / ``xla_compile_s`` attrs on the ambient span,
  so `explain(analyze=True)` and the JSONL trace show compile time on the
  operator that triggered it.
- Recompile-storm warning: when one program label crosses
  ``HYPERSPACE_COMPILE_STORM_THRESHOLD`` distinct traced shapes (default 32,
  0 disables), a `warnings.warn` fires ONCE for that label and
  ``xla.compiles.storm_warnings`` ticks — the silent-hang precursor (a
  non-quantized shape stream) becomes a loud, attributed signal.
- Fallback: on a jax build without ``jax.monitoring``, `observed_jit`
  instead watches the jitted callable's compile-cache size around each call
  and charges the call's wall time to a detected compile — coarser, but the
  counters stay nonzero.

`install` never imports jax itself — it is called from `observed_jit`, whose
call sites have jax imported by definition.
"""

from __future__ import annotations

import functools
import os
import threading
import warnings
from typing import Dict, Optional

from ..exceptions import CompileTimeoutError
from . import device_observatory as _devobs
from . import faults as _faults
from . import metrics as _metrics
from . import tracing as _tracing

ENV_STORM_THRESHOLD = "HYPERSPACE_COMPILE_STORM_THRESHOLD"
_DEFAULT_STORM_THRESHOLD = 32

#: Compile/dispatch deadline per `observed_jit` call (seconds; unset/0 = off).
#: The r05 TPU bench hung 2400 s inside ONE `bucket_id` compile with no
#: deadline and no attribution; with this set, the call runs under a watchdog
#: and a runaway compile becomes a classified, program-labeled
#: `CompileTimeoutError` instead of a silent hang. Whether a given call WILL
#: compile is not knowable up front, so the watchdog wraps every call: the
#: ~0.1 ms thread handoff per dispatch prices this as a build/bench/first-
#: deploy supervision knob, not a hot-serving default (docs/configuration.md).
ENV_COMPILE_TIMEOUT_S = "HYPERSPACE_COMPILE_TIMEOUT_S"

_DEADLINE_EXCEEDED = _metrics.counter("xla.compiles.deadline_exceeded")


def compile_timeout_s() -> float:
    try:
        return max(0.0, float(os.environ.get(ENV_COMPILE_TIMEOUT_S, "") or 0.0))
    except ValueError:
        return 0.0

_EVENT_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EVENT_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_EVENT_PREFIX = "/jax/compilation_cache/"

_COMPILES = _metrics.counter("xla.compiles.count")
_COMPILE_SECONDS = _metrics.histogram("xla.compiles.seconds")
_TRACES = _metrics.counter("xla.compiles.traces")
_STORMS = _metrics.counter("xla.compiles.storm_warnings")

_UNLABELED = "<unlabeled>"

_local = threading.local()  # per-thread label stack (compiles are synchronous)
_lock = threading.Lock()
_programs: Dict[str, dict] = {}
_installed = False
_have_monitoring = False


def storm_threshold() -> int:
    """Distinct traced shapes per program before the storm warning (0 = off)."""
    try:
        return int(
            os.environ.get(ENV_STORM_THRESHOLD, _DEFAULT_STORM_THRESHOLD)
            or _DEFAULT_STORM_THRESHOLD
        )
    except ValueError:
        return _DEFAULT_STORM_THRESHOLD


def _current_label() -> str:
    stack = getattr(_local, "stack", None)
    return stack[-1][0] if stack else _UNLABELED


def _mark_traced() -> bool:
    """Flag the innermost in-flight observed_jit call as having traced; returns
    whether this is the FIRST trace event of that call. One call can emit many
    jaxpr-trace events (shard_map programs trace one inner jaxpr per collective
    region — 16+ for one exchange), so per-label `traces` counts CALLS that
    traced a new shape, which is the unit the storm heuristic reasons about."""
    stack = getattr(_local, "stack", None)
    if not stack:
        return True  # unlabeled: keep raw event counting
    cell = stack[-1]
    if cell[1]:
        return False
    cell[1] = True
    return True


def _program(label: str) -> dict:
    with _lock:
        p = _programs.get(label)
        if p is None:
            p = _programs[label] = {
                "compiles": 0,
                "compile_s": 0.0,
                "traces": 0,
                "storm_warned": False,
            }
        return p


def _check_storm(label: str, p: dict) -> None:
    if label == _UNLABELED:
        # The unlabeled bucket aggregates every jit program OUTSIDE the
        # engine's declared entry points (jax-internal helpers, eager
        # dispatch fragments) — many distinct programs, so "distinct shapes
        # of one program" is meaningless there and would warn on any long
        # session. Storm detection applies to labeled programs only.
        return
    threshold = storm_threshold()
    if threshold <= 0:
        return
    with _lock:
        if p["storm_warned"] or p["traces"] < threshold:
            return
        p["storm_warned"] = True
    _STORMS.inc()
    warnings.warn(
        f"hyperspace compile storm: program '{label}' has traced "
        f"{p['traces']} distinct shapes (threshold {threshold}, "
        f"{p['compile_s']:.1f}s in backend compiles so far) — a shape that "
        f"is not pow2-quantized is likely recompiling per call; see "
        f"docs/observability.md (compile observatory)",
        RuntimeWarning,
        stacklevel=2,
    )


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    """jax.monitoring duration listener — called only when jax compiles."""
    if event == _EVENT_BACKEND_COMPILE:
        _COMPILES.inc()
        _COMPILE_SECONDS.observe(duration)
        label = _current_label()
        p = _program(label)
        with _lock:
            p["compiles"] += 1
            p["compile_s"] += float(duration)
        sp = _tracing.current_span()
        if sp is not None:
            sp.inc_attr("xla_compiles", 1)
            sp.inc_attr("xla_compile_s", round(float(duration), 6))
        # Per-query compile bill on the ledger too (the workload history
        # store's compile-storm hotspot axis); no-op without an open ledger.
        from . import accounting as _accounting

        _accounting.add("xla_compiles", 1)
        _accounting.add("xla_compile_s", round(float(duration), 6))
    elif event == _EVENT_JAXPR_TRACE:
        _TRACES.inc()
        if not _mark_traced():
            return  # later jaxpr of the SAME call: not a new program shape
        label = _current_label()
        p = _program(label)
        with _lock:
            p["traces"] += 1
        _check_storm(label, p)


_cache_events: Dict[str, int] = {}


def _on_event(event: str, **_kw) -> None:
    """Plain-event listener: persistent compile-cache traffic counters."""
    if event.startswith(_CACHE_EVENT_PREFIX):
        leaf = event[len(_CACHE_EVENT_PREFIX):].replace("/", ".")
        _metrics.counter(f"xla.compile_cache.{leaf}").inc()
        with _lock:
            _cache_events[leaf] = _cache_events.get(leaf, 0) + 1


def compile_cache_summary() -> dict:
    """Persistent-compilation-cache traffic: {"dir": the cache directory JAX
    uses or None, "events": {event leaf: count}}. A SECOND process (or a
    post-`jax.clear_caches()` re-dispatch) against a warm cache shows
    `cache_hits` > 0 here — the observable proof it paid zero backend
    compiles. Consumed by the exporter frames and `chip_smoke.py`."""
    import jax

    with _lock:
        events = dict(_cache_events)
    return {"dir": jax.config.jax_compilation_cache_dir or None, "events": events}


def install() -> bool:
    """Register the monitoring listeners once. Returns whether the
    ``jax.monitoring`` path is live (False = wrapper fallback mode). Callers
    have jax imported already; this never triggers the import."""
    global _installed, _have_monitoring
    with _lock:
        if _installed:
            return _have_monitoring
        _installed = True
    try:
        from jax import monitoring as _monitoring

        _monitoring.register_event_duration_secs_listener(_on_event_duration)
        _monitoring.register_event_listener(_on_event)
        _have_monitoring = True
    except Exception:
        _have_monitoring = False
    return _have_monitoring


def observed_jit(fun=None, *, label: Optional[str] = None, **jit_kwargs):
    """Drop-in `jax.jit` replacement that attributes compiles to a program
    label: ``observed_jit(f, static_argnums=(0,))`` or used as a decorator
    (optionally ``@observed_jit(label="hashing.bucket_id")``). The wrapper's
    per-call cost is one thread-local list push/pop — the compile accounting
    itself only runs inside actual compiles, via the `install` listener."""
    if fun is None:
        return lambda f: observed_jit(f, label=label, **jit_kwargs)
    import jax

    monitoring_live = install()
    lbl = label or f"{fun.__module__.rsplit('.', 1)[-1]}.{fun.__name__}"
    jitted = jax.jit(fun, **jit_kwargs)
    # Fallback compile detection when jax.monitoring is absent: the jitted
    # callable's cache growing across a call means that call compiled.
    cache_size = getattr(jitted, "_cache_size", None) if not monitoring_live else None

    from .. import resilience as _resilience

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        # Reliability hooks BEFORE dispatch: the `device.compile` fault point,
        # and the ambient query deadline — a deadlined query must not start
        # another potentially-compiling program.
        _faults.check("device.compile")
        _resilience.check_deadline(lbl)
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        cell = [lbl, False]  # [label, saw-a-trace-event-this-call]
        stack.append(cell)
        # Device-time probe (HYPERSPACE_DEVICE_TIMING): decided BEFORE
        # dispatch; one env read when off. See device_observatory.
        probe_t0 = _devobs.probe_start(lbl)
        if cache_size is not None:
            import time as _time

            before = cache_size()
            t0 = _time.monotonic()
        try:
            limit = compile_timeout_s()
            if limit > 0.0:
                out = _call_under_deadline(jitted, args, kwargs, lbl, limit, cell)
            else:
                out = jitted(*args, **kwargs)
            if probe_t0 is not None:
                _devobs.probe_finish(lbl, probe_t0, out, traced=cell[1])
            return out
        finally:
            stack.pop()
            if cache_size is not None and cache_size() > before:
                dur = _time.monotonic() - t0
                _COMPILES.inc()
                _TRACES.inc()
                _COMPILE_SECONDS.observe(dur)
                p = _program(lbl)
                with _lock:
                    p["compiles"] += 1
                    p["compile_s"] += dur
                    p["traces"] += 1
                _check_storm(lbl, p)

    wrapper._hyperspace_jitted = jitted  # the underlying jit object (tests)
    return wrapper


def _call_under_deadline(fn, args, kwargs, label: str, limit_s: float, cell=None):
    """Run one jitted call on a watchdog thread with a hard deadline. On
    timeout the caller gets a classified, program-attributed
    `CompileTimeoutError`; the abandoned daemon thread may finish its compile
    in the background (XLA compiles are not preemptible), but the query is no
    longer hostage to it. The worker pushes the CALLER's stack cell onto its
    own thread-local stack (so the trace flag lands where the caller's device
    probe reads it) and runs under a COPY of the caller's context, so the
    monitoring listener's span and ledger attribution — both contextvar
    reads — see the submitting query, not a blank worker context."""
    import contextvars as _contextvars

    result: list = []
    err: list = []
    ctx = _contextvars.copy_context()

    def run() -> None:
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(cell if cell is not None else [label, False])
        try:
            result.append(ctx.run(fn, *args, **kwargs))
        except BaseException as e:  # re-raised on the calling thread
            err.append(e)
        finally:
            stack.pop()

    t = threading.Thread(
        target=run, name=f"hyperspace-compile-watchdog:{label}", daemon=True
    )
    t.start()
    t.join(limit_s)
    if t.is_alive():
        _DEADLINE_EXCEEDED.inc()
        raise CompileTimeoutError(
            f"program '{label}' did not complete within "
            f"HYPERSPACE_COMPILE_TIMEOUT_S={limit_s:g}s — likely a runaway XLA "
            "compile (a shape stream that is not pow2-quantized recompiles per "
            "call); see docs/reliability.md",
            elapsed_s=limit_s,
            timeout_s=limit_s,
        )
    if err:
        raise err[0]
    return result[0]


def program_summary() -> dict:
    """Per-program compile stats: {label: {compiles, compile_s, traces}},
    labels sorted, JSON-serializable — consumed by the exporter frames and
    ``bench_detail.compile_observatory``."""
    with _lock:
        return {
            lbl: {
                "compiles": p["compiles"],
                "compile_s": round(p["compile_s"], 4),
                "traces": p["traces"],
            }
            for lbl, p in sorted(_programs.items())
        }


def reset_programs() -> None:
    """Zero the per-program stats IN PLACE (tests; the registry counters are
    reset separately via `metrics.reset`). Labels stay registered."""
    with _lock:
        for p in _programs.values():
            p.update(compiles=0, compile_s=0.0, traces=0, storm_warned=False)

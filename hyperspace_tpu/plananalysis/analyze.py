"""EXPLAIN ANALYZE: the plan tree re-rendered with runtime truth.

The static explain subsystem (`plan_analyzer`) compiles plans without running
them. This module EXECUTES the query once under a trace capture
(`telemetry.tracing.capture`) and renders the SAME physical tree annotated
per node with what actually happened: measured wall seconds, rows out,
cache/memo hit-or-miss, and the pipelined executors' stage spans
(probe/verify/gather/…) nested where they ran. Sections below the tree report
the optimizer-rule decisions (which index rule rewrote the plan, and why the
others sat out), and the per-query cache/metric
counter deltas.

Nodes with no span did not execute — the engine served them another way (a
fused/streamed parent, a memoized pair cache, a footer-only count). That is
reported as such rather than as zero: the annotated tree never claims time
that was not spent.

Entry points: `DataFrame.explain(analyze=True)` and
`Hyperspace.explain(df, analyze=True)`.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def _fmt_seconds(s: Optional[float]) -> str:
    if s is None:
        return "?"
    if s >= 1:
        return f"{s:.3f}s"
    return f"{s * 1000:.1f}ms"


# Per-node annotation attrs: cache verdicts plus the scan pushdown's
# row-group pruning outcome (set only when a scan actually pruned).
_VERDICT_KEYS = (
    "bucketed_cache",
    "pairs_memo",
    "row_groups_scanned",
    "row_groups_skipped",
    # Retries that happened under this node (resilience layer): a plan that
    # was correct-but-retried shows it inline, per operator.
    "io_retries",
)


def _subtree_verdict(span, children_of, key, rendered_ids, own_id, depth: int = 0):
    """First cache-verdict value for `key` in the span's subtree, WITHOUT
    crossing into another rendered node's spans (a join line must not show
    its child scan's verdict). The attr may land on an INNER span of the
    same node (`execute` delegating to the wrapped `execute_concat`) or on a
    delegate node's span that matches no rendered tree node (e.g. the
    temporary demoted bucketed scan a hybrid `ScanExec.execute` constructs)
    — the verdict still belongs on the line the user sees."""
    v = span.attrs.get(key)
    if v is not None or depth >= 4:
        return v
    for child in children_of.get(span.span_id, ()):
        if not child.name.startswith("op:"):
            continue
        nid = child.attrs.get("node_id")
        if nid is not None and nid != own_id and nid in rendered_ids:
            continue  # a different rendered node's span: its verdict is its own
        v = _subtree_verdict(child, children_of, key, rendered_ids, own_id, depth + 1)
        if v is not None:
            return v
    return None


def _node_annotation(span, children_of, rendered_ids) -> str:
    parts = [f"time={_fmt_seconds(span.duration_s)}"]
    rows = span.attrs.get("rows_out")
    if rows is not None:
        parts.append(f"rows={rows}")
    own_id = span.attrs.get("node_id")
    for key in _VERDICT_KEYS:
        v = _subtree_verdict(span, children_of, key, rendered_ids, own_id)
        if v is not None:
            parts.append(f"{key}={v}")
    compiles = span.attrs.get("xla_compiles")
    if compiles:
        # Compile-observatory delta: XLA compiles triggered while this span
        # was the ambient one (a cold operator shows its compile bill here).
        parts.append(
            f"compiles={compiles}"
            f"(+{_fmt_seconds(span.attrs.get('xla_compile_s') or 0.0)})"
        )
    if span.status != "ok":
        parts.append(f"status={span.status}")
    return "   [" + ", ".join(parts) + "]"


def _stage_lines(span, children_of, indent: int) -> List[str]:
    """The `<kind>:stages` children of one operator span, each rendered as
    one busy-time breakdown line from the summary the span carries (its
    ``<stage>_s`` attributes: stages overlap; their busy sum over wall is
    the overlap ratio — see `StageTimings`). A stages span that closed
    without a summary (a fallback or a failure) renders no line."""
    out: List[str] = []
    pad = "  " * indent + "     "
    for child in children_of.get(span.span_id, ()):
        if not child.name.endswith(":stages") or "stage_counts" not in child.attrs:
            continue
        kind = child.name.split(":", 1)[0]
        stages = [
            f"{st}={_fmt_seconds(child.attrs.get(f'{st}_s'))}"
            for st in child.attrs.get("stage_counts") or {}
        ]
        wall = child.attrs.get("wall_s")
        overlap = child.attrs.get("overlap_ratio")
        extra = []
        if wall is not None:
            extra.append(f"wall={_fmt_seconds(wall)}")
        if overlap is not None:
            extra.append(f"overlap={overlap}")
        mode = child.attrs.get("mode")
        if mode:
            extra.append(f"mode={mode}")
        out.append(
            pad
            + f"{kind} stages: "
            + " ".join(stages + extra)
        )
        # Multiway star joins carry per-dimension stage walls in the summary
        # (`star_dims`): render one line per dimension so the explain tree
        # shows WHERE a star probe spent its time, not just a bare node.
        if child.attrs.get("join_mode") == "star":
            for dim in child.attrs.get("star_dims") or ():
                if not isinstance(dim, dict):
                    continue
                cells = [f"dim[{dim.get('index', '?')}]:"]
                for k in ("pad_s", "probe_s", "verify_s"):
                    v = dim.get(k)
                    if v is not None:
                        cells.append(f"{k[:-2]}={_fmt_seconds(v)}")
                for k in ("buckets", "pairs", "memo"):
                    v = dim.get(k)
                    if v is not None:
                        cells.append(f"{k}={v}")
                out.append(pad + "  " + " ".join(cells))
    return out


def explain_analyze_string(df) -> str:
    """Execute `df` once under a trace and render the annotated plan tree."""
    import time as _time

    from .. import resilience
    from ..engine.physical import ExecContext
    from ..telemetry import accounting, metrics, tracing
    from . import attribution as _attribution
    from . import planner as _planner

    session = df.session
    snap0 = metrics.snapshot()
    with tracing.capture() as cap:
        # The resilience scope mirrors collect(): the planner's decisions
        # ride the QueryScope into pool workers here exactly as they do on
        # the production path, so the analyzed run IS the decided run.
        with resilience.query_scope("query:explain_analyze"):
            with tracing.query_span("query:explain_analyze") as root:
                with tracing.span("plan"):
                    phys = df.physical_plan()
                fp = df._attach_fingerprint(phys)
                pd = _planner.decide(phys, fp)
                with _planner.decisions_scope(pd):
                    t0 = _time.monotonic()
                    result = phys.execute(ExecContext(session))
                _planner.observe(
                    pd,
                    _time.monotonic() - t0,
                    stages=_attribution.query_stage_walls(),
                )
                root.set_attr("rows_out", int(result.num_rows))
                accounting.set_value("rows_produced", int(result.num_rows))
    snap1 = metrics.snapshot()
    trace = cap.trace
    if trace is None:  # defensive: capture always receives the root above
        return "EXPLAIN ANALYZE: no trace captured"

    # Operator spans join the rendered tree on node identity; the OUTERMOST
    # span of a node wins (a node may open several — e.g. execute over
    # execute_concat).
    by_node: Dict[int, object] = {}
    for s in trace.spans:
        nid = s.attrs.get("node_id")
        if nid is not None and nid not in by_node:
            by_node[nid] = s
    children_of = trace.spans_by_parent()

    lines: List[str] = []
    root_span = trace.root
    lines.append("=" * 61)
    lines.append("EXPLAIN ANALYZE")
    lines.append("=" * 61)
    lines.append(
        f"query_id={trace.query_id}  wall={_fmt_seconds(root_span.duration_s)}  "
        f"rows={result.num_rows}"
    )
    plan_spans = trace.find("plan")
    if plan_spans:
        lines.append(f"planning={_fmt_seconds(plan_spans[0].duration_s)}")
    lines.append("")

    rendered_ids = {id(n) for n in phys.collect_nodes()}

    def walk(node, indent: int) -> None:
        line = node.format_line(indent)
        span = by_node.get(id(node))
        if span is not None:
            line += _node_annotation(span, children_of, rendered_ids)
        else:
            line += "   [not executed: fused/streamed into parent or served from cache]"
        lines.append(line)
        if span is not None:
            lines.extend(_stage_lines(span, children_of, indent))
        for c in node.children():
            walk(c, indent + 1)

    walk(phys, 0)
    # Stage spans recorded under the ROOT (e.g. a streamed aggregate whose
    # operator span closed before the summary landed) still get shown.
    root_stages = _stage_lines(root_span, children_of, 0)
    if root_stages:
        lines.append("")
        lines.extend(root_stages)

    decisions: List[dict] = []
    for s in trace.spans:
        if s.name.startswith("rule:"):
            decisions.extend(s.attrs.get("decisions", ()))
    lines.append("")
    lines.append("Rule decisions:")
    if decisions:
        for d in decisions:
            verdict = "applied" if d.get("applied") else "skipped"
            detail = {
                k: v
                for k, v in d.items()
                if k not in ("rule", "applied") and v not in (None, [], {})
            }
            suffix = f"  {detail}" if detail else ""
            lines.append(f"  {d.get('rule')}: {verdict}{suffix}")
    else:
        lines.append("  (none recorded — no optimizer rules fired on this plan)")

    # Planner: the adaptive cost-based decisions this run executed under,
    # each knob's chosen arm with BOTH arms' predicted attributable cost and
    # the predicted-vs-actual drift ratio (`docs/planner.md`). Pinned knobs
    # (explicit env flags) are reported too — the planner never overrides
    # them, it only prices what the pin costs.
    lines.append("")
    lines.append("Planner:")
    if pd is None:
        lines.append(
            "  (off: HYPERSPACE_PLANNER=0 — env-flag defaults in force)"
        )
    else:
        from .costmodel import KNOBS as _knobs

        wall_actual = root_span.duration_s or 0.0
        lines.append(
            f"  calibration={pd.calibration_source}  "
            f"fingerprint={pd.fingerprint or '(not computed)'}"
        )
        for knob in _knobs:
            d = pd.decisions.get(knob)
            if d is None:
                continue
            drift = ""
            if d.predicted_s and d.predicted_s > 0:
                drift = f"  drift_x={wall_actual / d.predicted_s:.2f}"
            lines.append(
                f"  {knob}: {d.arm} [{d.source}]  "
                f"predicted={_fmt_seconds(d.predicted_s)}  "
                f"(alt {_planner.arm_label(d.alt)}: "
                f"{_fmt_seconds(d.predicted_alt_s)}){drift}"
            )
        lines.append(
            f"  actual wall={_fmt_seconds(wall_actual)}  "
            "(drift_x = wall / the arm's predicted attributable cost)"
        )

    # Resource ledger: what THIS query spent (exact per-query attribution —
    # the contextvar-scoped ledger, not the process-wide counters below).
    led = accounting.ledger_for(trace.query_id)
    lines.append("")
    lines.append("Resource ledger (this query):")
    if led is not None:
        d = led.to_dict()
        for key in sorted(k for k in d if k not in ("query_id", "name", "start_s")):
            lines.append(f"  {key}: {d[key]}")
    else:
        lines.append("  (no ledger recorded)")

    # Device cost section (device_observatory): the device/host wall split
    # from the sampled execute probes, the transfer bytes both ways, and the
    # pow2 padding tax. Probes ride HYPERSPACE_DEVICE_TIMING; without it the
    # split is unknown and says so rather than claiming host-only time.
    lines.append("")
    lines.append("Device cost (this query):")
    if led is not None:
        d = led.to_dict()
        dev_s = d.get("device_time_s")
        if dev_s is not None:
            wall = d.get("wall_s") or 0.0
            host_s = d.get("host_time_s", max(0.0, wall - dev_s))
            pct = f" ({dev_s / wall:.0%} of wall)" if wall else ""
            lines.append(
                f"  device={_fmt_seconds(dev_s)}{pct}  host={_fmt_seconds(host_s)}"
                "  (sampled execute probes; see docs/observability.md)"
            )
        else:
            lines.append(
                "  device/host split unknown (set HYPERSPACE_DEVICE_TIMING=1 "
                "to sample execute timing)"
            )
        h2d = d.get("device_upload_bytes")
        d2h = d.get("d2h_bytes")
        if h2d or d2h:
            lines.append(f"  transfers: h2d={h2d or 0}B d2h={d2h or 0}B")
        if d.get("pad_ratio") is not None:
            lines.append(
                f"  padding tax: payload={d.get('pad_bytes_payload', 0)}B "
                f"padded={d.get('pad_bytes_padded', 0)}B "
                f"pad_ratio={d['pad_ratio']}"
            )
    else:
        lines.append("  (no ledger recorded)")

    # Attribution: per-stage cost vectors (stage ledger) joined with knob
    # ownership and the planner's stage-grain predicted-vs-actual. Only
    # rendered when the query carried stage data (HYPERSPACE_STAGE_ATTRIBUTION
    # on and at least one labeled bracket ran).
    attr_lines = _attribution.explain_lines(led.to_dict() if led else None)
    if attr_lines:
        lines.append("")
        lines.extend(attr_lines)

    delta = metrics.counters_delta(snap0, snap1)
    lines.append("")
    # The registry is process-wide: under concurrent queries this section
    # includes their counters too (span-tree attributions above are exact).
    lines.append("Cache/metric deltas (process-wide during this query):")
    if delta:
        for name in sorted(delta):
            lines.append(f"  {name}: +{delta[name]}")
    else:
        lines.append("  (none)")
    return "\n".join(lines)

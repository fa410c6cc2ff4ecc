#!/usr/bin/env python
"""hstimeline: emit a query's span tree as a Chrome-trace/Perfetto timeline.

The JSONL span export (``HYPERSPACE_TRACE_FILE``) records every span of
every traced query — one JSON object per span, whole traces appended
atomically. This tool joins one query's spans back into a causal timeline:
one lane per stage (the ``<kind>:<stage>`` span of every stage bracket,
with numbered sub-lanes where a stage's spans ran at once on several
threads), one lane for operator spans, one for pool-worker families — the
`stage_ledger.chrome_trace` conversion, loadable in ``chrome://tracing`` or
Perfetto's legacy importer.

Usage:
    python tools/hstimeline.py TRACE_FILE [--query-id ID] [--list]
        [--out PATH]

- With no ``--query-id`` the NEWEST query in the file is converted.
- ``--list`` prints every query id in the file (with span counts) and exits.
- ``--out`` defaults to ``timeline-<query_id>.json`` in the cwd; ``-``
  writes the JSON to stdout.

Live capture needs no tool run at all: set ``HYPERSPACE_TIMELINE_DIR`` and
every root query writes its own ``timeline-<query_id>.json`` at close
(`telemetry.tracing._finalize`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hyperspace_tpu.telemetry import stage_ledger as _stage_ledger  # noqa: E402


def load_spans(path: str) -> Dict[str, List[dict]]:
    """Span dicts grouped by query_id, file order preserved (the exporter
    appends whole traces, so file order IS finalize order). Torn/garbled
    lines skip — the history reader's tolerance contract."""
    out: Dict[str, List[dict]] = {}
    with open(path, "r") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("query_id"):
                out.setdefault(str(rec["query_id"]), []).append(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Convert a hyperspace span-export JSONL into Chrome-trace JSON"
    )
    ap.add_argument("trace_file", help="HYPERSPACE_TRACE_FILE JSONL export")
    ap.add_argument(
        "--query-id", default=None, help="query to convert (default: newest)"
    )
    ap.add_argument(
        "--list", action="store_true", help="list query ids in the file and exit"
    )
    ap.add_argument(
        "--out",
        default=None,
        help="output path (default timeline-<query_id>.json; '-' for stdout)",
    )
    args = ap.parse_args(argv)

    try:
        by_query = load_spans(args.trace_file)
    except OSError as e:
        print(f"hstimeline: cannot read {args.trace_file}: {e}", file=sys.stderr)
        return 2
    if not by_query:
        print(f"hstimeline: no spans in {args.trace_file}", file=sys.stderr)
        return 2

    if args.list:
        for qid, spans in by_query.items():
            roots = [s for s in spans if s.get("parent_id") is None]
            name = roots[0].get("name") if roots else "?"
            print(f"{qid}  spans={len(spans)}  root={name}")
        return 0

    qid = args.query_id
    if qid is None:
        qid = next(reversed(by_query))  # newest: last appended trace
    spans = by_query.get(qid)
    if spans is None:
        print(
            f"hstimeline: query_id {qid!r} not in {args.trace_file} "
            f"({len(by_query)} queries; --list to enumerate)",
            file=sys.stderr,
        )
        return 2

    doc = _stage_ledger.chrome_trace(spans)
    lanes = doc.get("otherData", {}).get("lanes", [])
    if args.out == "-":
        json.dump(doc, sys.stdout, default=str)
        sys.stdout.write("\n")
        return 0
    out = args.out or f"timeline-{qid}.json"
    with open(out, "w") as fh:
        json.dump(doc, fh, default=str)
    print(
        f"hstimeline: wrote {out}  query_id={qid} events="
        f"{len(doc['traceEvents'])} lanes={len(lanes)}"
    )
    stage_lanes = [ln for ln in lanes if ln.startswith("stage:")]
    if stage_lanes:
        print(f"  stage lanes: {', '.join(stage_lanes)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

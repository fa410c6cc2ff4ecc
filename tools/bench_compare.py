#!/usr/bin/env python
"""Compare two bench JSON artifacts and gate on timing regressions.

This tool makes the comparison of two bench artifacts mechanical: flatten both files to dotted numeric leaves,
report per-metric deltas, and exit non-zero when any TIMING metric regressed
past a configurable threshold.

Usage:
    python tools/bench_compare.py BASELINE.json CANDIDATE.json \
        [--threshold 0.25] [--min-seconds 0.01] [--keys glob ...] [--all] \
        [--history DIR]

``--history DIR`` additionally gates against the WORKLOAD HISTORY STORE
(`hyperspace_tpu.telemetry.history` — the same on-lake segments
`tools/hsreport.py` reads): for every plan-class fingerprint with enough
observed history, the p50 wall of the most recent ``--history-recent``
queries is compared against the class baseline p50 (all older records +
compacted checkpoints) under the same threshold/noise-floor rules. A bench
run that landed its ledgers in the store (``HYPERSPACE_HISTORY=1``) is then
regression-gated per plan class, not just per static bench key. With
``--history`` given, the static BASELINE/CANDIDATE pair becomes optional.

Semantics:
- A metric is a TIMING (lower is better) when its dotted key's leaf ends in
  ``_s`` or ``_seconds`` (``build_s``, ``indexed_cold_s``,
  ``agg_stream_warm_p50_s``, …). Only timings gate the exit code; counters
  and byte totals are reported informationally (with ``--all``).
- Regression = ``candidate > baseline * (1 + threshold)`` AND both values ≥
  ``--min-seconds`` (sub-noise timings never gate — a 2 ms blip is machine
  jitter, not a regression).
- ``--keys`` restricts gating to metrics whose dotted key matches any of the
  given ``fnmatch`` globs (reporting still covers everything shown).
- Bench files wrap their payload as ``{"bench_detail": {...}}`` (the
  driver's tail-parse contract); bare dicts work too.

Exit codes: 0 = no gated regressions, 1 = regressions found, 2 = usage or
unreadable/unparseable input.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import sys
from typing import Dict, List, Tuple


def flatten(obj, prefix: str = "") -> Dict[str, float]:
    """Dotted-key map of every numeric leaf (bools excluded; lists index by
    position). Non-numeric leaves are dropped — the comparison is about
    measurements, not labels."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}{i}."))
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, (int, float)):
        out[prefix[:-1]] = float(obj)
    return out


def load_bench(path: str) -> Dict[str, float]:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "bench_detail" in data:
        data = data["bench_detail"]
    return flatten(data)


def is_timing(key: str) -> bool:
    leaf = key.rsplit(".", 1)[-1]
    return leaf.endswith("_s") or leaf.endswith("_seconds")


def compare(
    base: Dict[str, float],
    cand: Dict[str, float],
    threshold: float,
    min_seconds: float,
    key_globs: List[str],
) -> Tuple[List[tuple], List[tuple]]:
    """(rows, regressions): rows = (key, base, cand, delta, ratio, flag) for
    every shared key; regressions = the gated subset."""
    rows, regressions = [], []
    for key in sorted(set(base) & set(cand)):
        b, c = base[key], cand[key]
        delta = c - b
        ratio = (c / b) if b else (float("inf") if c else 1.0)
        gated = (
            is_timing(key)
            and (not key_globs or any(fnmatch.fnmatch(key, g) for g in key_globs))
            and b >= min_seconds
            and c >= min_seconds
            and c > b * (1.0 + threshold)
        )
        rows.append((key, b, c, delta, ratio, gated))
        if gated:
            regressions.append((key, b, c, delta, ratio))
    return rows, regressions


def _history_rows(
    dir_path: str, threshold: float, min_seconds: float, recent_k: int
):
    """(rows, regressions) per plan-class fingerprint: recent-window p50
    wall vs the class baseline p50 from the history store. Built on the
    store's OWN reader + `recent_vs_baseline` (the exact computation
    `tools/hsreport.py`'s drift table renders), restricted here to credible
    classes: a full recent window and ≥ ANOMALY_MIN_SAMPLES of baseline."""
    import os as _os
    import sys as _sys

    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from hyperspace_tpu.telemetry import history as _history

    raw, checkpoints = _history.split_records(_history.iter_records(dir_path))
    rows, regressions = [], []
    for d in _history.recent_vs_baseline(
        raw,
        checkpoints,
        recent_k,
        min_baseline=_history.ANOMALY_MIN_SAMPLES,
        require_full_window=True,
    ):
        base_p50, recent_p50 = d["expected_p50_s"], d["actual_p50_s"]
        names = ",".join(d["names"]) or "?"
        key = f"history.{d['fingerprint']}[{names}].wall_p50_s"
        delta = recent_p50 - base_p50
        ratio = (recent_p50 / base_p50) if base_p50 else float("inf")
        gated = (
            base_p50 >= min_seconds
            and recent_p50 >= min_seconds
            and recent_p50 > base_p50 * (1.0 + threshold)
        )
        rows.append((key, base_p50, recent_p50, delta, ratio, gated))
        if gated:
            regressions.append((key, base_p50, recent_p50, delta, ratio))
    return rows, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "baseline", nargs="?", help="baseline bench JSON (e.g. BENCH_r01.json)"
    )
    ap.add_argument(
        "candidate", nargs="?", help="candidate bench JSON (e.g. BENCH_r05.json)"
    )
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.25,
        help="allowed fractional timing regression before failing (default 0.25)",
    )
    ap.add_argument(
        "--min-seconds",
        type=float,
        default=0.01,
        help="timings below this on either side never gate (noise floor, default 0.01)",
    )
    ap.add_argument(
        "--keys",
        nargs="*",
        default=[],
        help="fnmatch globs restricting which timing keys gate (default: all)",
    )
    ap.add_argument(
        "--all",
        action="store_true",
        help="report every shared numeric leaf, not just timings",
    )
    ap.add_argument(
        "--history",
        default=None,
        metavar="DIR",
        help="workload-history dir: additionally gate per-fingerprint "
        "recent p50 wall vs the stored class baseline",
    )
    ap.add_argument(
        "--history-recent",
        type=int,
        default=5,
        help="how many newest ledgers per class form the judged window "
        "(default 5)",
    )
    args = ap.parse_args(argv)
    if not args.history and not (args.baseline and args.candidate):
        ap.error("BASELINE and CANDIDATE are required unless --history is given")
    if (args.baseline is None) != (args.candidate is None):
        # One positional alone (candidate forgotten) must be a loud error:
        # silently skipping the static gate would let CI read green off the
        # history gate alone while believing the bench pair was compared.
        ap.error("BASELINE and CANDIDATE must be given together")

    rows, regressions = [], []
    if args.baseline and args.candidate:
        try:
            base = load_bench(args.baseline)
            cand = load_bench(args.candidate)
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_compare: cannot read inputs: {e}", file=sys.stderr)
            return 2
        if not base or not cand:
            print("bench_compare: no numeric leaves found", file=sys.stderr)
            return 2

        rows, regressions = compare(
            base, cand, args.threshold, args.min_seconds, args.keys
        )
        shared = [r for r in rows if args.all or is_timing(r[0])]
        print(
            f"bench_compare: {args.baseline} -> {args.candidate}  "
            f"({len(rows)} shared metrics, threshold {args.threshold:+.0%}, "
            f"noise floor {args.min_seconds}s)"
        )
        for key, b, c, delta, ratio, gated in shared:
            mark = "  REGRESSION" if gated else ""
            print(f"  {key}: {b:.6g} -> {c:.6g}  ({delta:+.6g}, x{ratio:.3f}){mark}")
        only_base = sorted(set(base) - set(cand))
        only_cand = sorted(set(cand) - set(base))
        if only_base:
            print(f"  ({len(only_base)} metrics only in baseline)")
        if only_cand:
            print(f"  ({len(only_cand)} metrics only in candidate)")

    if args.history:
        import os

        if not os.path.isdir(args.history):
            # A silently-empty gate is worse than a loud one: a wrong path
            # (or a producing step that moved its output) must fail the CI
            # leg, not print "0 gateable classes" and pass green forever.
            print(
                f"bench_compare: --history is not a directory: {args.history}",
                file=sys.stderr,
            )
            return 2
        try:
            h_rows, h_regs = _history_rows(
                args.history, args.threshold, args.min_seconds, args.history_recent
            )
        except (OSError, ImportError) as e:
            print(f"bench_compare: cannot read history dir: {e}", file=sys.stderr)
            return 2
        print(
            f"bench_compare history gate: {args.history}  "
            f"({len(h_rows)} gateable plan classes, recent window "
            f"{args.history_recent})"
        )
        for key, b, c, delta, ratio, gated in h_rows:
            mark = "  REGRESSION" if gated else ""
            print(f"  {key}: {b:.6g} -> {c:.6g}  ({delta:+.6g}, x{ratio:.3f}){mark}")
        rows.extend(h_rows)
        regressions.extend(h_regs)

    if regressions:
        print(
            f"FAIL: {len(regressions)} timing metric(s) regressed past "
            f"{args.threshold:+.0%}",
            file=sys.stderr,
        )
        return 1
    print("OK: no gated timing regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())

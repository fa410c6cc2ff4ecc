"""From a JAX profiler trace of the window to the numbers the benchmark reports.

`extract` reads the `.xplane.pb` the profiler wrote into plain lists: device
events per chip and line, and host spans per thread (the program's spans enter
`jax.profiler.TraceAnnotation` while it traces). The harness marks the window
on the device itself, with two tiny programs (`START_MARK`, `END_MARK`) run
just inside its ends: a host annotation as long as the window was not
recorded on the chip (PR 22's first traces). `reduce` turns those lists into busy and idle time,
the device programs that took most time, the idle gaps labelled by the host
span open at the time, and device time per program. Both are plain functions
of their input, so a small recorded extract tests them (tests/).
"""

import glob
import os
import re

import numpy as np

START_MARK = "jit_bench_window_start"
END_MARK = "jit_bench_window_end"
# Lines of a TPU plane: one event per XLA program run, and one per op in it.
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_HOST_SKIP = ("tf_", "ThreadpoolListener")


def extract(trace_dir: str) -> dict:
    """{"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": {thread: [[name, start_ns, dur_ns], ...]}} from the newest trace
    under `trace_dir`. Raises when the profiler wrote none."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
            if lines:
                out["device"][plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith(_HOST_SKIP):
                    continue
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events if e.duration_ns > 0]
                if evs:
                    out["host"][line.name] = evs
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def program_name(event_name: str) -> str:
    """`jit__probe(12)` -> `jit__probe`: the program, without the run's id."""
    return re.sub(r"\(\d+\)$", "", event_name)


def window(ex: dict) -> tuple:
    """(start, end) ns: from the end of the last start mark to the start of
    the end mark after it, on the first chip that ran both. (The start mark
    is also run once to compile it, which the trace may show.)"""
    for lines in ex["device"].values():
        marks = sorted((s, s + d, program_name(n)) for n, s, d in lines.get(MODULE_LINE, []))
        starts = [e for s, e, n in marks if n == START_MARK]
        ends = [s for s, e, n in marks if n == END_MARK and starts and s >= starts[-1]]
        if starts and ends:
            return starts[-1], ends[0]
    raise ValueError(f"no {START_MARK}/{END_MARK} pair in the trace")


def reduce(ex: dict, n_chips: int = 1, top: int = 10, spans=(), t_mark: float = 0.0) -> dict:
    """busy_s (mean over the chips used), window_s, idle_share, device_ops
    (programs by total device seconds), idle_gaps (idle seconds by the
    innermost host span open at the gap's middle), program_s (all programs'
    device seconds), program_s_in_span ({span name: {program: device
    seconds}} of the program runs whose middle lies inside a span of that
    name, over `spans` alone).

    `spans` are the program's own spans (`HYPERSPACE_TRACE_FILE` lines, wall
    clock `start_s`), put on the trace's clock by `t_mark`: the wall time at
    which the start mark had finished on the device."""
    w0, w1 = window(ex)
    host = [[s["name"], w0 + (s["start_s"] - t_mark) * 1e9, s["duration_s"] * 1e9] for s in spans]
    ex = dict(ex, host=dict(ex["host"], **{"program spans": host}))
    planes = sorted(ex["device"])[:n_chips]
    if not planes:
        raise ValueError("the trace holds no device plane: no operation ran on a device")
    busy = []
    gaps = {}
    program_s = {}
    program_s_in_span = {}
    own_s = np.array([s for _, s, _ in host], dtype=np.float64)
    own_e = np.array([s + d for _, s, d in host], dtype=np.float64)
    own_name = np.array([n for n, _, _ in host], dtype=object)
    spans = [(s, s + d, name) for evs in ex["host"].values() for name, s, d in evs]
    span_s = np.array([s for s, _, _ in spans], dtype=np.float64)
    span_e = np.array([e for _, e, _ in spans], dtype=np.float64)
    for plane in planes:
        lines = ex["device"][plane]
        evs = lines.get(OP_LINE) or lines.get(MODULE_LINE, [])
        merged = _union([(max(s, w0), min(s + d, w1)) for _, s, d in evs if s + d > w0 and s < w1])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, d in lines.get(MODULE_LINE, []):
            if s + d > w0 and s < w1 and program_name(name) not in (START_MARK, END_MARK):
                p = program_name(name)
                sec = (min(s + d, w1) - max(s, w0)) / 1e9
                program_s[p] = program_s.get(p, 0.0) + sec
                mid = s + d / 2
                for span in set(own_name[(own_s <= mid) & (own_e >= mid)]):
                    per = program_s_in_span.setdefault(span, {})
                    per[p] = per.get(p, 0.0) + sec
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            covering = np.flatnonzero((span_s <= mid) & (span_e >= mid))
            if len(covering):
                label = spans[covering[np.argmin(span_e[covering] - span_s[covering])]][2]
            else:
                label = "(no span open: no request in flight)"
            gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(planes)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / len(busy) if busy else 0.0
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": sorted(([k, v / len(planes)] for k, v in program_s.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
        "program_s": {k: v / len(planes) for k, v in program_s.items()},
        "program_s_in_span": {
            span: {k: v / len(planes) for k, v in per.items()} for span, per in program_s_in_span.items()
        },
    }

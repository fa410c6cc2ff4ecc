"""The index build's own spans in a traced window, for the readers of
`decode_ms`, `sort_ms`, `write_ms` and `outside_pipeline_ms`.

The program writes its spans to `HYPERSPACE_TRACE_FILE`, which run.py points
at `<workdir>/spans.jsonl` for the window and keeps until the readers have
run. Each build's pipeline is one `build:stages` span, and its bucketize and
sort one `build:sort` child. The four readers split one build, the window's
median build: the one whose `build:stages` wall is the median (the lower of
the two middle ones). So decode + sort + write is the median pipeline wall,
and with the rest of that build's cycle the cycle's wall, by construction;
medians taken apart per part would not add up. A program whose
`build:stages` spans were made after the build (attribute `synthesized`) has
no real stage spans, and gives nothing to read.
"""

import json
import os

SPANS_FILE = "spans.jsonl"


def builds(ctx) -> list:
    """[(stages, sort)] span dicts, one pair per build, in the order the
    builds started; [] when the window's span file holds no real
    `build:stages` span."""
    path = os.path.join(ctx.run.workdir, SPANS_FILE)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        spans = [json.loads(line) for line in f if line.strip()]
    sorts = {(s["query_id"], s["parent_id"]): s for s in spans if s["name"] == "build:sort"}
    pairs = []
    for s in spans:
        if s["name"] != "build:stages" or s["attrs"].get("synthesized"):
            continue
        sort = sorts.get((s["query_id"], s["span_id"]))
        if sort is not None:
            pairs.append((s, sort))
    return sorted(pairs, key=lambda p: p[0]["start_s"])


def median_build(ctx):
    """(stages, sort, record) of the window's median build, or None. The
    record is the harness's record of that build's cycle: a cycle builds
    once, so the n-th build in start order is the n-th record's; None when
    the counts differ."""
    pairs = builds(ctx)
    if not pairs:
        return None
    records = sorted(ctx.run.records, key=lambda r: r["t_start"])
    if len(records) != len(pairs):
        records = [None] * len(pairs)
    order = sorted(range(len(pairs)), key=lambda i: pairs[i][0]["duration_s"])
    i = order[(len(order) - 1) // 2]
    return pairs[i] + (records[i],)


def end_s(span: dict) -> float:
    return span["start_s"] + span["duration_s"]

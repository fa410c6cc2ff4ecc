"""The mesh build's exchange in the window's median build (build_spans.py):
the walls of its `build:exchange_counts` (the count pass and the host read of
its matrix) and `build:exchange` (the exchange program on every chip, to its
outputs being ready) spans, children of that build's `build:stages`. None
when the build ran no exchange, as a build on one chip does."""
import json
import os

from build_spans import SPANS_FILE, median_build

STAGES = ("build:exchange_counts", "build:exchange")


def read(ctx):
    b = median_build(ctx)
    if b is None:
        return None
    stages = b[0]
    with open(os.path.join(ctx.run.workdir, SPANS_FILE)) as f:
        spans = [json.loads(line) for line in f if line.strip()]
    walls = [
        s["duration_s"]
        for s in spans
        if s["name"] in STAGES and s["query_id"] == stages["query_id"] and s["parent_id"] == stages["span_id"]
    ]
    return 1e3 * sum(walls) if walls else None

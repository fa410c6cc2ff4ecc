"""The build's bucketize-and-sort against the HBM roofline: the least time to
read the key and the covering payload once and write them once, over the
device time of the build's sort programs in the window.

The bytes are those of the operation, whatever implements it. The programs
are found by the names the trace shows, and only where they run inside a
build: inside the program's `build:stages` span, which spans the whole wall of
one build's pipeline (telemetry/tracing.record_stage_spans). Its `build:sort`
child cannot place them: it is synthesized with the sort's busy seconds from
the build's start, not from when the sort ran. So a program of the same name
run outside a build is not counted: `jit_impl` is
`partition.fused_bucketize_sort` (its inner function is named `impl`),
`jit__sort_perm` is `partition.sort_perm`, `jit__sort_pallas_call` is
`pallas.sort`. A later PR that renames them, or the span, leaves this metric
silent.
"""
from readers import roofline_pct

SPAN = "build:stages"
PROGRAMS = {"jit_impl", "jit__sort_perm", "jit__sort_pallas_call"}


def read(ctx):
    run = ctx.run
    spec = run.cfg["indexes"][run.traffic["index"]]
    width = 8 * (len(spec["indexed"]) + len(spec["included"]))  # every column is 8 bytes wide
    rows = run.queries.source_rows(run, run.traffic["index"]) * len(run.records)
    in_span = ctx.trace["program_s_in_span"].get(SPAN, {})
    return roofline_pct(ctx, sum(in_span.get(p, 0.0) for p in PROGRAMS), 2.0 * rows * width)

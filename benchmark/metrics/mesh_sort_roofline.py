"""The mesh build's exchange and local sort against the HBM roofline: the
least time each chip needs to read its share of the key and the covering
payload once and write it once, over its device time in the window's
`build:exchange_counts` and `build:exchange` spans.

The bytes are those of the operation, as for `build_sort_roofline`:
2 × rows × 8 × (indexed + included columns) a build, divided over the cell's
chips, because the trace's device time is a mean per chip. The programs are
found by span and not by name: every program whose run has its middle inside
one of the two spans counts, so a renamed program still does. None when no
exchange ran."""
from readers import roofline_pct

SPANS = ("build:exchange_counts", "build:exchange")


def read(ctx):
    run = ctx.run
    spec = run.cfg["indexes"][run.traffic["index"]]
    width = 8 * (len(spec["indexed"]) + len(spec["included"]))  # every column is 8 bytes wide
    rows = run.queries.source_rows(run, run.traffic["index"]) * len(run.records)
    in_span = ctx.trace["program_s_in_span"]
    device_s = sum(sum(in_span.get(span, {}).values()) for span in SPANS)
    return roofline_pct(ctx, device_s, 2.0 * rows * width / run.cell["chips"])

"""From the start of the pipeline (`build:stages`) to the start of its
bucketize and sort (`build:sort`), in the window's median build
(build_spans.py): the source files decoded and their keys staged to the
device."""
from build_spans import median_build


def read(ctx):
    b = median_build(ctx)
    return None if b is None else 1e3 * (b[1]["start_s"] - b[0]["start_s"])

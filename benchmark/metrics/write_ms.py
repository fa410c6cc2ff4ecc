"""From the end of `build:sort` to the end of the pipeline (`build:stages`),
in the window's median build (build_spans.py): the wait for the writer's
column concat, the column takes and the bucket files encoded and written."""
from build_spans import end_s, median_build


def read(ctx):
    b = median_build(ctx)
    return None if b is None else 1e3 * (end_s(b[0]) - end_s(b[1]))

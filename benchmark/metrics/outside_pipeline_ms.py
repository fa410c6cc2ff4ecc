"""The cycle of the window's median build (build_spans.py) less its
pipeline: the harness's own wall of the cycle (`t_done - t_start` of its
record) minus the wall of `build:stages`. That is the planning, the
operation-log writes (`log:*`), the staging commit (`build:commit`), and
the delete and vacuum of the previous copy."""
from build_spans import median_build


def read(ctx):
    b = median_build(ctx)
    if b is None or b[2] is None:
        return None
    stages, _, record = b
    return 1e3 * ((record["t_done"] - record["t_start"]) - stages["duration_s"])

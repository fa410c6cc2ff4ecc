"""The wall of `build:sort` in the window's median build (build_spans.py):
the fused bucketize-and-sort program dispatched, run on the device, and its
permutation and bucket ids copied back to the host."""
from build_spans import median_build


def read(ctx):
    b = median_build(ctx)
    return None if b is None else 1e3 * b[1]["duration_s"]

"""The plain check of a TPC-H SF 10 covering index: the SF 1 check
(`reference/tpch_sf1.py`), its five numbers and their limits, with one
shortcut for the size.

The SF 1 check lexsorts every row of the source and of the index by all
four columns: 14 s at SF 1's 6 M rows on one CPU core, minutes at 60 M
rows. So this check first tries to
prove every number 0 with passes that cost about one sort by key: each file
named for one bucket, one file for each non-empty bucket and no other, every
row of a file in that file's bucket under the format's hash, each file sorted
by key, and the index's rows, stably sorted by key, equal to the source's
stably sorted by key. The last holds for an index written in the build's
canonical order, which keeps one key's rows in source order. What passes
has all five numbers 0; anything else, an index in another order among
it, is counted by the SF 1 check itself.
"""

import glob
import os

import numpy as np

import harness

_SF1 = harness.config_module("reference", "tpch_sf1")
LIMITS = _SF1.LIMITS
bucket_of = _SF1.bucket_of


def _sound(data: dict, index_spec: dict, num_buckets: int, index_dir: str) -> bool:
    """Whether the index provably gives 0 on every number of the SF 1 check."""
    import pyarrow.parquet as pq

    key = index_spec["indexed"][0]
    cols = [key] + list(index_spec["included"])
    src = data[index_spec["table"]]
    files = sorted(glob.glob(os.path.join(index_dir, "**", "*.parquet"), recursive=True))
    named = [_SF1._BUCKET_FILE.search(os.path.basename(f)) for f in files]
    file_bucket = [int(m.group(1)) if m else -1 for m in named]
    want_buckets = set(np.unique(bucket_of(src[key], num_buckets)).tolist())
    if len(set(file_bucket)) != len(files) or set(file_bucket) != want_buckets:
        return False
    parts = []
    for b, f in zip(file_bucket, files):
        t = pq.read_table(f, columns=cols)
        k = t.column(key).to_numpy()
        if (np.diff(k) < 0).any() or (bucket_of(k, num_buckets) != b).any():
            return False
        parts.append(t)
    got = {c: np.concatenate([t.column(c).to_numpy() for t in parts]) for c in cols}
    if len(got[key]) != len(src[key]):
        return False
    a = np.argsort(src[key], kind="stable")
    b = np.argsort(got[key], kind="stable")
    return all(np.array_equal(src[c][a], got[c][b]) for c in cols)


def check_index(data: dict, index_spec: dict, num_buckets: int, index_dir: str) -> dict:
    """The SF 1 check's numbers for a built covering index against its source."""
    if _sound(data, index_spec, num_buckets, index_dir):
        return dict.fromkeys(LIMITS, 0)
    return _SF1.check_index(data, index_spec, num_buckets, index_dir)


def check(run) -> dict:
    """The numbers compared: the index the window built last."""
    index_dir = os.path.join(run.workdir, "lake", "indexes", run.records[-1]["params"])
    spec = run.cfg["indexes"][run.traffic["index"]]
    return check_index(run.data, spec, int(run.cfg["num_buckets"]), index_dir)

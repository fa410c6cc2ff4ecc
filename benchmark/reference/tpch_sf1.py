"""The plain check of a TPC-H SF 1 covering index, in numpy over the generated
arrays and the index's parquet files read back with pyarrow.

Imports nothing of the system under test. The bucket of a key is derived
here from the index format's hash, written out from its definition: the
murmur3 32-bit finalizer over the key's two 32-bit words (low, then high) with
seed 0x9747B28C, each word folded in as `h = (h ^ fmix32(w)) * 5 + 0xE6546B64`,
then `fmix32(h) % num_buckets`; bucket b is the file `part-<b:05d>.parquet`,
and an empty bucket has no file.
"""

import glob
import os
import re

import numpy as np

# Each number compared, with its limit (PERF.md gives the readings each limit
# was set from). All exact.
LIMITS = {
    "index_rows_mismatched": 0,
    "index_rows_in_wrong_bucket": 0,
    "index_bucket_files_off": 0,
    "index_keys_in_two_buckets": 0,
    "index_unsorted_buckets": 0,
}

SEED = np.uint32(0x9747B28C)
_BUCKET_FILE = re.compile(r"part-(\d+)\.parquet$")


def _fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def bucket_of(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """The bucket of each int64 key under the index format's hash."""
    u = np.ascontiguousarray(keys, dtype=np.int64).view(np.uint64)
    h = np.full(len(u), SEED, dtype=np.uint32)
    for w in ((u & np.uint64(0xFFFFFFFF)).astype(np.uint32), (u >> np.uint64(32)).astype(np.uint32)):
        h = (h ^ _fmix32(w)) * np.uint32(5) + np.uint32(0xE6546B64)
    return (_fmix32(h) % np.uint32(num_buckets)).astype(np.int64)


def check_index(data: dict, index_spec: dict, num_buckets: int, index_dir: str) -> dict:
    """A built covering index against its source: every source row exactly
    once (key and included columns), every row in the file of its key's
    bucket, one file for each non-empty bucket and no other, each key in one
    file, each file sorted by key."""
    import pyarrow.parquet as pq

    key = index_spec["indexed"][0]
    cols = [key] + list(index_spec["included"])
    src = data[index_spec["table"]]
    files = sorted(glob.glob(os.path.join(index_dir, "**", "*.parquet"), recursive=True))
    named = [_BUCKET_FILE.search(os.path.basename(f)) for f in files]
    file_bucket = [int(m.group(1)) if m else -1 for m in named]
    want_buckets = set(np.unique(bucket_of(src[key], num_buckets)).tolist())
    files_off = len(set(file_bucket) ^ want_buckets) + len(file_bucket) - len(set(file_bucket))

    parts = [pq.read_table(f, columns=cols) for f in files]
    unsorted = wrong_bucket = 0
    bucket_keys = []
    for b, t in zip(file_bucket, parts):
        k = t.column(key).to_numpy()
        unsorted += int(len(k) > 1 and not (np.diff(k) >= 0).all())
        wrong_bucket += int((bucket_of(k, num_buckets) != b).sum())
        bucket_keys.append(np.unique(k))
    all_keys = np.concatenate(bucket_keys) if bucket_keys else np.zeros(0, np.int64)
    _, counts = np.unique(all_keys, return_counts=True)

    got = {c: np.concatenate([t.column(c).to_numpy() for t in parts]) if parts else np.zeros(0) for c in cols}
    n_src, n_got = len(src[key]), len(got[key])
    mismatched = abs(n_src - n_got)
    if n_src == n_got:
        a = np.lexsort([src[c] for c in reversed(cols)])
        b = np.lexsort([got[c] for c in reversed(cols)])
        differ = np.zeros(n_src, dtype=bool)
        for c in cols:
            differ |= src[c][a] != got[c][b]
        mismatched = int(differ.sum())
    return {
        "index_rows_mismatched": int(mismatched),
        "index_rows_in_wrong_bucket": int(wrong_bucket),
        "index_bucket_files_off": int(files_off),
        "index_keys_in_two_buckets": int((counts > 1).sum()),
        "index_unsorted_buckets": int(unsorted),
    }


def check(run) -> dict:
    """The numbers compared: the index the window built last."""
    index_dir = os.path.join(run.workdir, "lake", "indexes", run.records[-1]["params"])
    spec = run.cfg["indexes"][run.traffic["index"]]
    return check_index(run.data, spec, int(run.cfg["num_buckets"]), index_dir)

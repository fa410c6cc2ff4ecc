"""The controls of the build cell's comparison: the reference's own index put
in the program's place, each with one fault that would tempt a later PR, at
the cell's own size. Each has to come out above a limit (PERF.md gives the
readings).

    python benchmark/control.py --workload <cell> --seeds 1 2 3

- `unmerged`: bucket files take the source files' rows as a parallel decode
  would hand them over, last file first, with no merge by key, and bucket by
  key mod the bucket count instead of the format's hash.
- `one_bucket`: every row in one sorted file, as a build that skips the
  bucketize would write it.
- `shifted_hash`: sorted bucket files, each row one bucket past its hash's.

Needs no accelerator: numpy and pyarrow only. The benchmark's own runs never
run it.
"""

import argparse
import json
import os
import shutil
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def _unmerged(key: np.ndarray, nb: int, ref):
    order = np.concatenate(np.array_split(np.arange(len(key)), 8)[::-1])
    return order, key[order] % nb


def _one_bucket(key: np.ndarray, nb: int, ref):
    order = np.argsort(key, kind="stable")
    return order, np.zeros(len(key), np.int64)


def _shifted_hash(key: np.ndarray, nb: int, ref):
    order = np.argsort(key, kind="stable")
    return order, (ref.bucket_of(key[order], nb) + 1) % nb


FAULTS = {"unmerged": _unmerged, "one_bucket": _one_bucket, "shifted_hash": _shifted_hash}


def write_index(data: dict, spec: dict, num_buckets: int, out_dir: str, fault: str, ref) -> None:
    """The source's rows as bucket files `part-<b>.parquet`, placed and
    ordered as `fault` says."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    cols = [spec["indexed"][0]] + list(spec["included"])
    src = data[spec["table"]]
    order, bucket = FAULTS[fault](src[cols[0]], num_buckets, ref)
    os.makedirs(out_dir, exist_ok=True)
    for b in np.unique(bucket):
        rows = order[bucket == b]
        pq.write_table(pa.table({c: src[c][rows] for c in cols}), os.path.join(out_dir, f"part-{b:05d}.parquet"))


def control_numbers(cell: dict, seed: int, workdir: str) -> dict:
    """{fault: the numbers compared} for one seed."""
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        cfg = cell["config"]
        data = harness.config_module("datagen", cell["config_name"]).generate(cfg, seed)
        ref = harness.config_module("reference", cell["config_name"])
        spec = cfg["indexes"][cell["traffic"]["index"]]
        out = {}
        for fault in FAULTS:
            index_dir = os.path.join(workdir, fault)
            write_index(data, spec, int(cfg["num_buckets"]), index_dir, fault, ref)
            out[fault] = ref.check_index(data, spec, int(cfg["num_buckets"]), index_dir)
            shutil.rmtree(index_dir)
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    limits = harness.config_module("reference", cell["config_name"]).LIMITS
    for seed in args.seeds:
        per_fault = control_numbers(cell, seed, os.path.join(harness.ROOT, ".bench_work", "control"))
        for fault, numbers in per_fault.items():
            failed = sorted(k for k, v in numbers.items() if v > limits[k])
            print(json.dumps({"workload": args.workload, "seed": seed, "control": fault, "numbers": numbers, "fails": failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

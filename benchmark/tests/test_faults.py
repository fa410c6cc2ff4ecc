"""A run with the timed path broken underneath has to come out not correct:
a build that leaves the index as it was, a build of half the rows, a build
that puts every row in one bucket, and one whose hash is shifted by a bucket."""

import numpy as np
import pytest

import run as bench_run
from conftest import small_cell

SEED = 2**31 + 7


def _break_build(monkeypatch, how):
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.index import build_pipeline
    from hyperspace_tpu.ops import hashing

    real_create, real_bucket_id, real_decode = Hyperspace.create_index, hashing.bucket_id, build_pipeline._decode_file
    calls = []

    def create(self, df, cfg):
        calls.append(cfg.index_name)
        if how == "unchanged" and len(calls) > 1:
            return None  # the index is left as it was: no new copy written
        return real_create(self, df, cfg)

    def decode_half(*args, **kwargs):  # each source file's first half of rows alone
        t = real_decode(*args, **kwargs)
        return t.take(np.arange(t.num_rows // 2))

    def bucket_id(columns, device_arrays, num_buckets):
        b = real_bucket_id(columns, device_arrays, num_buckets)
        return b * 0 if how == "one_bucket" else (b + 1) % num_buckets

    monkeypatch.setattr(Hyperspace, "create_index", create)
    if how == "half":
        monkeypatch.setattr(build_pipeline, "_decode_file", decode_half)
    if how in ("one_bucket", "shifted_hash"):
        monkeypatch.setattr(hashing, "bucket_id", bucket_id)


@pytest.mark.parametrize("how", ["unchanged", "half", "one_bucket", "shifted_hash"])
def test_broken_build_is_not_correct(monkeypatch, workdir, how):
    from hyperspace_tpu.exceptions import HyperspaceException

    _break_build(monkeypatch, how)
    try:
        res = bench_run.run_cell(small_cell("tpch_sf1.build"), SEED, 2.0, False, workdir, log=lambda *_: None)
    except HyperspaceException:
        # The window's delete of a copy that was never written raises: the
        # run prints no result, which is not correct either.
        assert how == "unchanged"
        return
    assert not res["correct"], res["checks"]

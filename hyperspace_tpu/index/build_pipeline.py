"""Pipelined covering-index build: overlap decode, transfer, sort, and writes.

The serial build (`CoveringIndexBuilder.write` before this module) is a chain:
decode ALL parquet files → concat on host → one bucketize+sort → per-bucket
writes. At the 8M-row bench scale the device spends ~0.2 s sorting inside a
~5 s build — everything else is host work the device waits on. The reference
design hid exactly this behind Spark's pipelined shuffle executors
(PAPER.md §0); this module is the TPU-native equivalent, shaped like a
training input pipeline:

1. **Decode pool** (``HYPERSPACE_BUILD_DECODE_THREADS``): source files decode
   concurrently (pyarrow C++ releases the GIL) through the per-file scan
   cache, each decoded file split into row chunks of at most
   ``HYPERSPACE_BUILD_CHUNK_ROWS``.
2. **Hash / transfer stage**: as each chunk lands, its bucket ids are computed
   (CPU backend) or its key columns are padded to pow2 rows and
   ``jax.device_put`` onto the device (device backend) — staging overlaps the
   remaining decodes instead of serializing after them. Pow2 quantization
   bounds the set of transfer/compile shapes; the staged buffers are donated
   to the sort program, so XLA reuses their memory.
3. **Fused bucketize+sort**: on the device path the bucket hash, chunk
   concatenation, and the stable variadic sort run as ONE jitted program
   (`ops.partition.fused_bucketize_sort_perm`), or the Pallas in-VMEM bitonic
   composite sort for small builds (`pallas_composite_build_sort`). On the
   CPU backend the permutation comes from the exact same
   `ops.partition.host_sort_perm` the serial path uses.
4. **Writer pool** (``HYPERSPACE_BUILD_WRITERS``): while the sort runs, each
   output column is assembled into one arrow array in Arrow's memory pool
   (the columns concurrently). The sorted order is then split at bucket
   starts into up to one row range per writer; each range gathers every
   column through its slice of ``perm``, and its bucket files encode from
   zero-copy slices of that range's arrays as soon as its gather lands.

**Determinism contract**: the pipelined build produces BYTE-IDENTICAL index
files to the serial path, for any thread counts. The global row order is
fixed by the same (file order, chunk concat order) the serial concat uses;
bucket hashing is elementwise; the sort permutation comes from the identical
sort implementation over identical arrays; and bucket rows gathered through
``perm[lo:hi]`` equal ``sorted_table[lo:hi]`` by construction, whichever
gather range holds them.
``HYPERSPACE_BUILD_DECODE_THREADS=1`` bypasses this module entirely and runs
the pre-pipeline serial code path (`tests/test_build_pipeline.py` pins the
two to each other).

The ordering this contract fixes is the engine's ONE canonical build order —
stable (bucket, keys...) with ties broken by original row id — which the
MESH build (`parallel/table_ops.distributed_bucketize_perm`, taken instead
of this pipeline's decode and sort when a multi-device mesh claims the
source) also produces, and writes through this module's writer
(`write_sorted`): all three build strategies (serial, pipelined, mesh) emit
byte-identical index files, pinned by `tests/test_build_pipeline.py` and
`tests/test_mesh_compile.py` respectively. Any change to the sort tie order
here breaks BOTH contracts at once.

Every stage runs inside `StageTimings.timed`, the one bracket behind the
busy-time sums, the stage ledger and the spans: under ``build:stages`` (the
pipeline's wall) each file decoded is a ``build:decode`` span, each chunk's
key staging ``build:h2d`` (``build:hash`` on the CPU), then ``build:sort``,
the writer's ``build:concat``, one ``build:take`` per gather range and one
``build:write`` per non-empty bucket — each on the thread that did the work,
with its ``rows``/``bytes``/``file``/``bucket``/``buckets``/``columns``
counts as attributes. The
summary (busy seconds per stage, wall, overlap ratio) is recorded via
`telemetry.profiling.record_build_stages` and surfaced in `bench.py`'s
``bench_detail``.
"""

from __future__ import annotations

import os
import queue
import threading
from concurrent.futures import (
    FIRST_EXCEPTION,
    ThreadPoolExecutor,
    as_completed,
    wait,
)
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..config import IndexConstants
from ..engine import io as engine_io
from ..engine.encoding import column_nbytes
from ..engine.schema import STRING
from ..engine.table import Column, Table
from ..exceptions import HyperspaceException
from ..telemetry.profiling import StageTimings, record_build_stages

# The decode-pool knob is defined in engine.io (`decode_pool_size`) — ONE
# threading contract shared by the build pipeline, `read_files`, and the
# streaming query executor; re-exported here for existing importers.
from ..engine.io import ENV_DECODE_THREADS

ENV_WRITERS = "HYPERSPACE_BUILD_WRITERS"
ENV_CHUNK_ROWS = "HYPERSPACE_BUILD_CHUNK_ROWS"

_DEFAULT_WRITERS = 8
_DEFAULT_CHUNK_ROWS = 4_000_000


@dataclass(frozen=True)
class PipelineConfig:
    """Env-tunable pipeline knobs. ``decode_threads == 1`` means "serial
    fallback": the caller runs the pre-pipeline code path unchanged."""

    decode_threads: int
    writers: int
    chunk_rows: int

    @staticmethod
    def from_env(n_files: int) -> "PipelineConfig":
        # Shared parse (`engine.io.decode_pool_size`): `1` = serial fallback,
        # explicit values cap at the file count. The build floors n_files at 2
        # so the default still pipelines single-file sources (the
        # decode-threads value doubles as the pipelined-vs-serial flag here).
        decode = engine_io.decode_pool_size(max(2, n_files))
        writers = max(1, int(os.environ.get(ENV_WRITERS, _DEFAULT_WRITERS) or _DEFAULT_WRITERS))
        chunk_rows = max(
            1, int(os.environ.get(ENV_CHUNK_ROWS, _DEFAULT_CHUNK_ROWS) or _DEFAULT_CHUNK_ROWS)
        )
        return PipelineConfig(decode_threads=decode, writers=writers, chunk_rows=chunk_rows)

    @property
    def pipelined(self) -> bool:
        return self.decode_threads != 1


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _lineage_column(path: str, n: int) -> Column:
    """The per-file `_data_file_name` column: value-identical to the serial
    path's `Table.from_pydict({...: [path] * n})` (dictionary [path], codes 0)."""
    return Column(STRING, np.zeros(n, dtype=np.int32), np.asarray([path]))


def _decode_file(
    path: str,
    file_format: str,
    wanted: Optional[List[str]],
    partitions,
    lineage: bool,
) -> Table:
    """One file's decoded, decorated table — the per-file unit of the serial
    path (`read_files` semantics incl. partition columns + scan cache), plus
    the lineage column when enabled."""
    file_cols = engine_io.file_columns_for(wanted, partitions)
    t = engine_io.file_table(path, file_format, file_cols)
    t = engine_io.decorate_file_table(t, path, partitions, wanted)
    if lineage:
        cols = dict(t.columns)
        cols[IndexConstants.DATA_FILE_NAME_COLUMN] = _lineage_column(path, t.num_rows)
        t = Table(cols)
    return t


def _effective_chunk_rows(cfg: PipelineConfig) -> int:
    """Sub-file chunking exists to QUANTIZE DEVICE TRANSFERS (bound staging
    buffer sizes); on the CPU path it would only force re-concatenation
    copies, so the chunk is the whole file/table there."""
    from ..ops.backend import use_device_path

    return cfg.chunk_rows if use_device_path() else (1 << 62)


def _split_chunks(t: Table, chunk_rows: int) -> List[Table]:
    """Row-slice a decoded file table into pipeline chunks (numpy views — the
    chunk boundaries have no effect on output order or values)."""
    if t.num_rows <= chunk_rows:
        return [t]
    out = []
    for lo in range(0, t.num_rows, chunk_rows):
        hi = min(lo + chunk_rows, t.num_rows)
        out.append(
            Table(
                {
                    n: Column(
                        c.dtype,
                        c.data[lo:hi],
                        c.dictionary,
                        None if c.validity is None else c.validity[lo:hi],
                    )
                    for n, c in t.columns.items()
                }
            )
        )
    return out


def _concat_key_columns(chunks: List[Table], key_names: List[str]) -> List[Column]:
    """Global key columns in concat order. Deliberately THE `Table.concat`
    implementation (the serial path's concat), restricted to the key columns —
    the bit-for-bit contract depends on identical union-dictionary/promotion/
    validity behavior, so there must be exactly one copy of that logic.
    `Table.concat` returns the single table unchanged (no copies) for the
    warm one-chunk case."""
    merged = Table.concat([t.select(key_names) for t in chunks])
    return [merged.column(n) for n in key_names]


def _sort_pipeline(
    chunks: List[Table],
    chunk_bucket_ids: List[Optional[np.ndarray]],
    staged_device: Optional[List[List["object"]]],
    key_names: List[str],
    num_buckets: int,
    stages: StageTimings,
) -> Tuple[np.ndarray, np.ndarray]:
    """The sort stage: global (perm, starts) over the chunk concat order."""
    from ..ops.backend import use_device_path
    from ..ops.partition import (
        _sort_perm,
        _sortable,
        bucket_starts,
        fused_bucketize_sort_perm,
        host_sort_perm,
        pallas_composite_build_sort,
    )

    n = sum(t.num_rows for t in chunks)
    if not use_device_path():
        with stages.timed("sort", rows=n):
            b_host = (
                np.concatenate(chunk_bucket_ids)
                if chunk_bucket_ids
                else np.empty(0, np.int32)
            )
            key_cols = _concat_key_columns(chunks, key_names)
            perm = host_sort_perm(b_host, key_cols, num_buckets)
            sorted_b = b_host[perm]
    elif staged_device is not None:
        # Numeric keys, staged while decoding: hash+concat+sort in ONE
        # donated-buffer program (or the Pallas composite sort when the whole
        # build fits VMEM).
        with stages.timed("sort", rows=n):
            valid_lens = [t.num_rows for t in chunks]
            perm = sorted_b = None
            if len(key_names) == 1 and len(staged_device[0]) >= 1:
                import jax.numpy as jnp

                from ..ops.hashing import bucket_id

                if _pow2_ceil(max(n, 1)) <= 32768:
                    key_dev = jnp.concatenate(
                        [c[:v] for c, v in zip(staged_device[0], valid_lens)]
                    )
                    key_cols = _concat_key_columns(chunks, key_names)
                    b_dev = bucket_id(key_cols, [key_dev], num_buckets)
                    res = pallas_composite_build_sort(b_dev, key_dev, n, num_buckets)
                    if res is not None:
                        perm, sorted_b = res
            if perm is None:
                perm, sorted_b = fused_bucketize_sort_perm(
                    staged_device, valid_lens, num_buckets
                )
    else:
        # Device path, but the keys need host-side union-dictionary encoding
        # (strings) — replicate the serial device program over the global
        # key columns.
        import jax.numpy as jnp

        from ..ops.hashing import bucket_id

        with stages.timed("concat"):
            key_cols = _concat_key_columns(chunks, key_names)
        with stages.timed("h2d", bytes=sum(c.data.nbytes for c in key_cols)):
            arrs = [jnp.asarray(c.data) for c in key_cols]
        with stages.timed("sort", rows=n):
            b = bucket_id(key_cols, arrs, num_buckets)
            perm_d, sorted_b_d = _sort_perm(
                b, tuple(_sortable(a) for a in arrs), n
            )
            perm = np.asarray(perm_d)
            sorted_b = np.asarray(sorted_b_d)
    return perm, bucket_starts(sorted_b, num_buckets)


def _gather_ranges(starts: np.ndarray, parts: int) -> List[Tuple[int, int]]:
    """Split the sorted order into up to `parts` gather ranges of about equal
    rows, cut only at bucket starts: [(b0, b1)] bucket ranges, contiguous,
    each holding rows ``[starts[b0], starts[b1])`` (at least one), together
    covering every row. A bucket is never split, so a build with one huge
    bucket, or fewer non-empty buckets than `parts`, gets fewer ranges, and
    a build of no rows none."""
    n = int(starts[-1])
    cuts = [0]
    for i in range(1, parts):
        # The first bucket that starts at or past the i-th share of the rows.
        b = int(np.searchsorted(starts, i * n / parts))
        if starts[cuts[-1]] < starts[b] < n:
            cuts.append(b)
    cuts.append(len(starts) - 1)
    return [(b0, b1) for b0, b1 in zip(cuts, cuts[1:]) if starts[b1] > starts[b0]]


class _BucketWriter:
    """Writer-pool stage: gather into sorted order + parquet encode, GIL-free.

    `prepare()` assembles ONE arrow array per output column over the chunk
    concatenation — decoded values + null mask, exactly what the serial path's
    `table_to_arrow` feeds the writer. A numeric column of several chunks is
    each chunk wrapped without a copy and joined by `pa.concat_arrays` into
    Arrow's memory pool (which releases the GIL and keeps its pages from
    build to build); one chunk is wrapped as it is. The columns assemble
    concurrently. Under encoded execution (``HYPERSPACE_ENCODED_EXEC``),
    string columns stay CODES end to end instead: the chunk columns re-encode
    over their union dictionary (the exact `Table.concat` implementation the
    serial path runs), the gather moves int32 codes, and `write_bucket` emits
    a compacted `pa.DictionaryArray` through the SAME
    `encoding.dictionary_arrow_array` helper the serial `table_to_arrow`
    uses — so serial == pipelined stays byte-identical in both flag states,
    and the N decoded strings never materialize.

    `run()` splits the sorted order at bucket starts into up to one gather
    range per writer (`_gather_ranges`). Each range takes every column
    through its slice of `perm` with `pyarrow.compute.take`, and as soon as
    it lands, each of its buckets is handed to the pool: `write_bucket`
    encodes a zero-copy slice of the range's arrays — the same values as a
    slice of a whole-column take, so the parquet bytes are too. Take and
    encode are C++ paths that release the GIL, so the pool runs gathers and
    encodes truly in parallel, with no barrier between them.

    `prepare()` is designed to run on its own thread OVERLAPPED with the sort
    stage: the sort only touches the key columns, the writers need them all."""

    def __init__(self, chunks: List[Table], index_data_path: str, stages: StageTimings):
        self.chunks = chunks
        self.names = chunks[0].column_names
        self.index_data_path = index_data_path
        self.stages = stages
        self.arrays: Dict[str, "object"] = {}
        self.dicts: Dict[str, np.ndarray] = {}  # union dict of encoded string cols
        # bucket -> (first row of its gather range, the range's arrays)
        self.ranges: Dict[int, Tuple[int, Dict[str, "object"]]] = {}

    def prepare(self, pool_size: int) -> None:
        from ..engine import encoding as _encoding

        encode = _encoding.encoded_exec_enabled()
        with self.stages.timed("concat") as sp:
            with ThreadPoolExecutor(max_workers=min(pool_size, len(self.names))) as pool:
                built = list(pool.map(lambda n: self._assemble(n, encode), self.names))
            copied = 0
            for name, (arr, dictionary, nbytes) in zip(self.names, built):
                self.arrays[name] = arr
                if dictionary is not None:
                    self.dicts[name] = dictionary
                copied += nbytes
            if sp.recording:
                sp.set_attr("bytes", copied)

    def _assemble(self, name: str, encode: bool):
        """(arrow array, union dictionary | None, bytes copied) of one output
        column over the chunk concatenation."""
        import pyarrow as pa

        cols = [t.column(name) for t in self.chunks]
        if cols[0].is_string:
            mask = None
            if any(c.validity is not None for c in cols):
                mask = ~np.concatenate(
                    [
                        c.validity
                        if c.validity is not None
                        else np.ones(len(c), dtype=bool)
                        for c in cols
                    ]
                )
            if encode:
                # Encoded path: ONE union re-encode over the chunk
                # dictionaries (`Table.concat` — the serial concat's own
                # implementation, so codes and dictionary are bit-equal to
                # the serial path's) — the gather then moves int32 codes,
                # never decoded strings.
                mc = Table.concat([Table({name: c}) for c in cols]).column(name)
                arr = pa.array(mc.data, mask=mask)
                return arr, mc.dictionary, arr.nbytes if len(cols) > 1 else 0
            # Decoded fallback: decode per chunk through its own dictionary —
            # value-identical to the serial union-dictionary decode.
            values = np.concatenate([c.dictionary[c.data] for c in cols])
            arr = pa.array(values, mask=mask)
            return arr, None, arr.nbytes
        # Numeric: each chunk wrapped without a copy (values, and its own null
        # mask), promoted as `np.concatenate` would promote mixed dtypes.
        dtype = np.result_type(*(c.data.dtype for c in cols))
        parts = [
            pa.array(
                c.data.astype(dtype, copy=False),
                mask=None if c.validity is None else ~c.validity,
            )
            for c in cols
        ]
        if len(parts) == 1:
            return parts[0], None, 0
        arr = pa.concat_arrays(parts)
        return arr, None, arr.nbytes

    def _bucket_array(self, n: str, sl):
        """One column's arrow array for a bucket, from its slice `sl` of the
        gathered range: the slice itself, or — for encoded string columns —
        the compacted dictionary array built from the sliced codes (the
        shared write-side primitive)."""
        from ..engine import encoding as _encoding

        if n not in self.dicts:
            return sl
        if sl.null_count:
            mask = np.asarray(sl.is_null())
            codes = np.asarray(sl.fill_null(0))
        else:
            mask = None
            codes = np.asarray(sl)
        return _encoding.dictionary_arrow_array(codes, self.dicts[n], mask)

    def write_bucket(self, b: int, lo: int, hi: int) -> Optional[str]:
        """Write bucket `b` (rows [lo, hi) of the sorted order, inside one
        gathered range); returns the file's path, None for an empty bucket."""
        if hi <= lo:
            return None  # empty bucket: no file (same contract as the serial path)
        import pyarrow as pa

        row0, arrays = self.ranges[b]
        path = os.path.join(self.index_data_path, f"part-{b:05d}.parquet")
        out = pa.table(
            {
                n: self._bucket_array(n, arrays[n].slice(lo - row0, hi - lo))
                for n in self.names
            }
        )
        # Bounded row groups over the key-sorted bucket rows: the footer zone
        # maps then resolve point/range filters INSIDE the bucket file (scan
        # pushdown). Same bound as the serial writer — the byte-identity
        # contract between the two paths includes the row-group layout, and
        # both paths now write through ONE `storage.write` fault/retry site.
        engine_io.checked_write_table(
            out, path, row_group_rows=engine_io.index_row_group_rows()
        )
        return path

    def run(self, perm: np.ndarray, starts: np.ndarray, pool_size: int) -> None:
        import pyarrow as pa

        idx = pa.array(perm)
        with ThreadPoolExecutor(max_workers=pool_size) as pool:
            gathers = {
                pool.submit(self._gather, idx, starts, b0, b1): (b0, b1)
                for b0, b1 in _gather_ranges(starts, pool_size)
            }
            writes = []
            for f in as_completed(gathers):
                b0, b1 = gathers[f]
                row0, arrays = int(starts[b0]), f.result()
                for b in range(b0, b1):
                    if starts[b + 1] > starts[b]:  # an empty bucket has no file
                        self.ranges[b] = (row0, arrays)
                        writes.append(
                            pool.submit(
                                self._timed_bucket, b, int(starts[b]), int(starts[b + 1])
                            )
                        )
            # Every range is gathered: release the pre-gather copy, so peak
            # memory stays at ~one extra full-table copy, like the serial
            # path's sorted_table.
            self.arrays.clear()
            done, _ = wait(writes, return_when=FIRST_EXCEPTION)
            for f in done:
                f.result()  # re-raise the first worker failure

    def _gather(self, idx, starts: np.ndarray, b0: int, b1: int) -> Dict[str, "object"]:
        """Every column's rows [starts[b0], starts[b1]) of the sorted order."""
        import pyarrow.compute as pc

        lo, hi = int(starts[b0]), int(starts[b1])
        with self.stages.timed(
            "take",
            rows=hi - lo,
            buckets=int(np.count_nonzero(np.diff(starts[b0 : b1 + 1]))),
            columns=len(self.names),
        ):
            rows = idx.slice(lo, hi - lo)
            return {n: pc.take(self.arrays[n], rows) for n in self.names}

    def _timed_bucket(self, b: int, lo: int, hi: int) -> None:
        with self.stages.timed("write", bucket=b, rows=hi - lo) as sp:
            path = self.write_bucket(b, lo, hi)
        # The file's size is read after the bracket, so the span times the
        # write alone; an ended span still takes attributes until its root
        # ends.
        if sp.recording and path is not None:
            sp.set_attr("bytes", os.path.getsize(path))


def pipelined_write(
    files_in_order: List[str],
    file_format: str,
    wanted: Optional[List[str]],
    partitions,
    lineage: bool,
    key_names: List[str],
    num_buckets: int,
    index_data_path: str,
    cfg: PipelineConfig,
) -> dict:
    """Run the staged build: decode → hash/stage → fused sort → bucket writes.
    Returns the stage-timing summary (also recorded in telemetry)."""
    if not files_in_order:
        raise HyperspaceException("No data files to read.")
    from ..ops.backend import use_device_path

    mode = "pipelined-device" if use_device_path() else "pipelined-cpu"
    with StageTimings("build", mode) as stages:
        n_files = len(files_in_order)

        # Warm-source shortcut: when the exact concat this build would assemble
        # is already cached (a prior query or build read the same files +
        # columns), the whole decode stage collapses to reusing it — the
        # "reuse scan_cache entries when warm" contract, one level up.
        if not lineage:
            _, cached_concat = engine_io.concat_cache_probe(
                files_in_order, file_format, wanted, partitions
            )
            if cached_concat is not None:
                stages.add("decode", 0.0)
                return _finish_from_chunks(
                    _split_chunks(cached_concat, _effective_chunk_rows(cfg)),
                    key_names,
                    num_buckets,
                    index_data_path,
                    cfg,
                    stages,
                    n_files,
                )

        return _decode_and_finish(
            files_in_order,
            file_format,
            wanted,
            partitions,
            lineage,
            key_names,
            num_buckets,
            index_data_path,
            cfg,
            stages,
        )


def _stage_chunk_device(key_cols: List[Column], stages: StageTimings) -> List["object"]:
    """Pad a chunk's key arrays to pow2 rows and transfer (device path).
    Pow2-quantized staging bounds the set of buffer shapes the fused sort
    program compiles against (and that the compile cache must hold) to log2
    variety; the buffers are later DONATED to the sort program."""
    import jax

    with stages.timed("h2d") as sp:
        bufs = []
        staged = 0
        for c in key_cols:
            pad_n = _pow2_ceil(len(c.data))
            host = c.data
            if pad_n != len(host):
                host = np.concatenate([host, np.zeros(pad_n - len(host), host.dtype)])
            bufs.append(jax.device_put(host))
            staged += host.nbytes
        sp.set_attr("bytes", staged)
        return bufs


def _hash_chunk(key_cols: List[Column], num_buckets: int, stages: StageTimings) -> np.ndarray:
    """One chunk's bucket ids (CPU path) — elementwise, so the per-chunk
    concat equals the serial whole-table hash."""
    import jax.numpy as jnp

    from ..ops.hashing import bucket_id

    with stages.timed("hash", rows=len(key_cols[0].data)):
        arrs = [jnp.asarray(c.data) for c in key_cols]
        return np.asarray(bucket_id(key_cols, arrs, num_buckets))


def _stage_or_hash_chunk(
    ch: Table,
    key_names: List[str],
    num_buckets: int,
    device: bool,
    stages: StageTimings,
):
    """(staged device buffers | None, bucket ids | None) for one chunk — THE
    staging decision, shared by the streaming and warm-concat paths so they
    can never diverge (string keys need host union-dictionary encoding and
    disqualify the fused device staging)."""
    key_cols = [ch.column(k) for k in key_names]
    if device:
        if any(c.is_string for c in key_cols):
            return None, None
        return _stage_chunk_device(key_cols, stages), None
    return None, _hash_chunk(key_cols, num_buckets, stages)


def _finish_from_chunks(
    chunks: List[Table],
    key_names: List[str],
    num_buckets: int,
    index_data_path: str,
    cfg: PipelineConfig,
    stages: StageTimings,
    n_files: int,
) -> dict:
    """Hash/stage the given chunks inline (no decode stage to overlap with),
    then run the shared sort + write tail."""
    from ..ops.backend import use_device_path

    device = use_device_path()
    bucket_ids: List[Optional[np.ndarray]] = []
    staged: List[Optional[List["object"]]] = []
    for ch in chunks:
        bufs, b = _stage_or_hash_chunk(ch, key_names, num_buckets, device, stages)
        staged.append(bufs)
        bucket_ids.append(b)
    staged_device = None
    if device and chunks and all(b is not None for b in staged):
        staged_device = [[bufs[k] for bufs in staged] for k in range(len(key_names))]
    return _sort_write_summarize(
        chunks,
        bucket_ids,
        staged_device,
        key_names,
        num_buckets,
        index_data_path,
        cfg,
        stages,
        n_files,
    )


def _decode_and_finish(
    files_in_order: List[str],
    file_format: str,
    wanted: Optional[List[str]],
    partitions,
    lineage: bool,
    key_names: List[str],
    num_buckets: int,
    index_data_path: str,
    cfg: PipelineConfig,
    stages: StageTimings,
) -> dict:
    n_files = len(files_in_order)
    from ..ops.backend import use_device_path

    # Per-file decoded tables land at their file's slot so the chunk order is
    # deterministic regardless of decode completion order.
    file_tables: List[Optional[Table]] = [None] * n_files
    hash_q: "queue.Queue[int | None]" = queue.Queue()

    from .. import resilience as _resilience
    from ..telemetry import accounting as _accounting
    from ..telemetry import faults as _faults

    led = _accounting.current_ledger()  # pool decodes charge the build's ledger
    sc = _resilience.current_scope()  # workers honor the build's deadline

    def decode_one(i: int) -> None:
        with _accounting.use_ledger(led), _resilience.use_scope(sc):
            _faults.check("pool.worker")
            with stages.timed("decode", file=i) as sp:
                t = _decode_file(
                    files_in_order[i], file_format, wanted, partitions, lineage
                )
                file_tables[i] = t
                if sp.recording:
                    sp.add_attrs(
                        rows=t.num_rows,
                        bytes=sum(column_nbytes(c) for c in t.columns.values()),
                    )
        hash_q.put(i)

    device = use_device_path()
    # Chunk state, filled by the hash/stage worker in completion order (the
    # values are per-chunk and order-independent; chunk identity is the slot).
    chunk_lists: List[Optional[List[Table]]] = [None] * n_files
    chunk_buckets: Dict[Tuple[int, int], np.ndarray] = {}
    staged: Dict[Tuple[int, int], List["object"]] = {}

    hash_err: List[BaseException] = []

    def hash_worker() -> None:
        """Single consumer overlapping per-chunk hash/transfer with the
        remaining decodes; jax dispatch stays single-threaded."""
        done = 0
        while done < n_files:
            i = hash_q.get()
            if i is None:
                return  # abort: a decode worker failed
            t = file_tables[i]
            chunks = _split_chunks(t, _effective_chunk_rows(cfg))
            chunk_lists[i] = chunks
            for j, ch in enumerate(chunks):
                bufs, b = _stage_or_hash_chunk(
                    ch, key_names, num_buckets, device, stages
                )
                if bufs is not None:
                    staged[(i, j)] = bufs
                if b is not None:
                    chunk_buckets[(i, j)] = b
            done += 1

    def hash_worker_guarded() -> None:
        try:
            hash_worker()
        except BaseException as e:  # surfaced after join — never swallowed
            hash_err.append(e)

    hasher = threading.Thread(target=hash_worker_guarded, daemon=True)
    hasher.start()
    try:
        with ThreadPoolExecutor(max_workers=min(cfg.decode_threads, n_files)) as pool:
            futs = [pool.submit(decode_one, i) for i in range(n_files)]
            done, _ = wait(futs, return_when=FIRST_EXCEPTION)
            for f in done:
                f.result()  # re-raise the first decode failure
    except BaseException:
        hash_q.put(None)  # unblock the hash worker before propagating
        raise
    hasher.join()
    if hash_err:
        raise hash_err[0]

    chunks: List[Table] = [c for cl in chunk_lists for c in (cl or [])]
    bucket_ids: List[Optional[np.ndarray]] = [
        chunk_buckets.get((i, j))
        for i, cl in enumerate(chunk_lists)
        for j in range(len(cl or []))
    ]
    staged_device = None
    if device:
        ordered = [
            staged.get((i, j))
            for i, cl in enumerate(chunk_lists)
            for j in range(len(cl or []))
        ]
        if all(bufs is not None for bufs in ordered) and ordered:
            # [key column][chunk] layout for the fused program.
            staged_device = [
                [bufs[k] for bufs in ordered] for k in range(len(key_names))
            ]
    return _sort_write_summarize(
        chunks,
        bucket_ids,
        staged_device,
        key_names,
        num_buckets,
        index_data_path,
        cfg,
        stages,
        n_files,
    )


def write_sorted(
    chunks: List[Table],
    sort: Callable[[], Tuple[np.ndarray, np.ndarray]],
    index_data_path: str,
    cfg: PipelineConfig,
    stages: StageTimings,
) -> np.ndarray:
    """THE build's sort-and-write tail, shared by the pipeline and the mesh
    build: `sort()` gives (perm, starts) over the chunk concatenation while
    the writer assembles every output column beside it (the sort touches only
    the key columns), then the range writer gathers and writes the bucket
    files. Returns `perm`."""
    os.makedirs(index_data_path, exist_ok=True)
    writer = _BucketWriter(chunks, index_data_path, stages)
    prep_err: List[BaseException] = []

    def prep_guarded() -> None:
        try:
            writer.prepare(cfg.writers)
        except BaseException as e:
            prep_err.append(e)

    # Arrow-array assembly (all columns) overlaps the sort (key columns only).
    prep = threading.Thread(target=prep_guarded, daemon=True)
    prep.start()
    perm, starts = sort()
    prep.join()
    if prep_err:
        raise prep_err[0]

    writer.run(perm, starts, cfg.writers)
    return perm


def _sort_write_summarize(
    chunks: List[Table],
    bucket_ids: List[Optional[np.ndarray]],
    staged_device,
    key_names: List[str],
    num_buckets: int,
    index_data_path: str,
    cfg: PipelineConfig,
    stages: StageTimings,
    n_files: int,
) -> dict:
    perm = write_sorted(
        chunks,
        lambda: _sort_pipeline(
            chunks, bucket_ids, staged_device, key_names, num_buckets, stages
        ),
        index_data_path,
        cfg,
        stages,
    )
    summary = stages.summary(
        rows=int(perm.shape[0]),
        files=n_files,
        chunks=len(chunks),
        decode_threads=cfg.decode_threads,
        writers=cfg.writers,
    )
    record_build_stages(summary)
    return summary

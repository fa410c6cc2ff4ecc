"""CoveringIndexBuilder: the engine-side implementation of index creation/refresh.

Parity: reference `actions/CreateActionBase.scala` — validates the source plan, builds
the IndexLogEntry (signature over source files, relation inventory, numBuckets from
conf), and writes the index data. The write path is TPU-native: one `lax.sort` over
(bucket_id, indexed columns) replaces Spark's repartition+shuffle+per-bucket-sort
(see `ops/partition.py`), then per-bucket parquet files are written under the
`part-<bucket>` naming contract the bucketed join scan relies on.
"""

from __future__ import annotations

import os
from typing import List, Optional

from ..actions.create import IndexerBuilder
from ..config import IndexConstants
from ..engine import io as engine_io
from ..engine.logical import ScanNode, SourceRelation
from ..engine.schema import STRING, Field, Schema
from ..engine.session import DataFrame, HyperspaceSession
from ..engine.table import Column, Table
from ..exceptions import HyperspaceException
from ..ops.partition import bucketize_table
from ..util.resolver_utils import resolve_all
from .index_config import IndexConfig
from .log_entry import (
    Content,
    CoveringIndexProperties,
    Directory,
    IndexLogEntry,
    LogicalPlanFingerprint,
    Relation,
    Signature,
    Source,
    SourcePlanProperties,
)
from .signatures import create_provider


class CoveringIndexBuilder(IndexerBuilder):
    def __init__(self, session: HyperspaceSession):
        self._session = session

    # -- validation (reference CreateAction.scala:44-64) --------------------

    def validate_source(self, df: DataFrame, index_config: IndexConfig) -> None:
        if not isinstance(df.plan, ScanNode):
            raise HyperspaceException(
                "Only creating index over a plain relation scan is supported."
            )
        schema_names = df.plan.output_schema.names
        cs = self._session.hs_conf.case_sensitive
        for group in (index_config.indexed_columns, index_config.included_columns):
            if resolve_all(group, schema_names, cs) is None:
                raise HyperspaceException(
                    f"Index config columns {group} could not be resolved against "
                    f"dataframe columns {schema_names}."
                )

    def _resolved_columns(self, df: DataFrame, index_config: IndexConfig):
        names = df.plan.output_schema.names
        cs = self._session.hs_conf.case_sensitive
        indexed = resolve_all(index_config.indexed_columns, names, cs)
        included = resolve_all(index_config.included_columns, names, cs)
        return indexed, included

    # -- the build (reference CreateActionBase.scala:119-191) ---------------

    def _missing_partition_columns(self, rel: SourceRelation, wanted: List[str]) -> List[str]:
        """Partition columns not already selected — lineage mode pulls them into the
        index so a lineage row can be mapped back to its source partition
        (reference `CreateActionBase.scala:176-188`)."""
        if rel.partition_spec is None:
            return []
        have = {w.lower() for w in wanted}
        return [c for c in rel.partition_spec.columns if c.lower() not in have]

    def _prepare_index_table(self, df: DataFrame, index_config: IndexConfig) -> Table:
        """Select indexed+included columns (+ lineage `_data_file_name` and missing
        partition columns when lineage is enabled)."""
        indexed, included = self._resolved_columns(df, index_config)
        rel = df.plan.relation
        wanted = indexed + included
        partitions = (
            None
            if rel.partition_spec is None
            else (rel.partition_spec, rel.root_paths)
        )
        if self._session.hs_conf.lineage_enabled:
            wanted = wanted + self._missing_partition_columns(rel, wanted)
            parts = []
            for f in rel.files:
                t = engine_io.read_files(
                    [f.path], rel.file_format, wanted, partitions=partitions
                )
                lineage = Table.from_pydict(
                    {IndexConstants.DATA_FILE_NAME_COLUMN: [f.path] * t.num_rows}
                )
                cols = dict(t.columns)
                cols[IndexConstants.DATA_FILE_NAME_COLUMN] = lineage.column(
                    IndexConstants.DATA_FILE_NAME_COLUMN
                )
                parts.append(Table(cols))
            return Table.concat(parts)
        files = [f.path for f in rel.files]
        return engine_io.read_files(files, rel.file_format, wanted, partitions=partitions)

    def write(self, df: DataFrame, index_config: IndexConfig, index_data_path: str) -> None:
        """The bucketed build. Routed three ways:

        - mesh build (distributed all_to_all) when a device mesh applies,
          written through the pipeline's range writer;
        - the staged PIPELINE (`index/build_pipeline.py`) by default — decode,
          transfer, fused bucketize+sort and bucket writes overlap;
        - the pre-pipeline SERIAL chain under
          ``HYPERSPACE_BUILD_DECODE_THREADS=1`` (the bit-for-bit reference
          the pipeline is pinned to by `tests/test_build_pipeline.py`).

        Crash-safe commit: the build writes into a dot-prefixed STAGING
        directory that every inventory/scan path ignores, committed to
        `index_data_path` by ONE atomic rename (`index/staging.py`). A failure
        deletes the staging dir; a SIGKILL at any point leaves either an
        invisible staging dir (reclaimed by the next action on the index) or
        the complete committed dir — never partial visible files for a later
        `Content.from_directory` inventory to pick up (the log entry stays
        uncommitted either way)."""
        from .staging import stage_commit

        with stage_commit(index_data_path) as stage:
            self._write_routed(df, index_config, stage)

    def _write_routed(
        self, df: DataFrame, index_config: IndexConfig, index_data_path: str
    ) -> None:
        from .build_pipeline import PipelineConfig, pipelined_write

        indexed, included = self._resolved_columns(df, index_config)
        num_buckets = self._session.hs_conf.num_buckets
        rel = df.plan.relation
        cfg = PipelineConfig.from_env(len(rel.files))
        if cfg.pipelined and not self._mesh_may_apply(rel):
            lineage = self._session.hs_conf.lineage_enabled
            wanted = indexed + included
            if lineage:
                wanted = wanted + self._missing_partition_columns(rel, wanted)
            partitions = (
                None
                if rel.partition_spec is None
                else (rel.partition_spec, rel.root_paths)
            )
            files_in_order = (
                # Lineage reads per file in inventory order; the plain path
                # rides `read_files`, which sorts — the pipeline's chunk
                # order must match the serial concat order exactly.
                [f.path for f in rel.files]
                if lineage
                else sorted(f.path for f in rel.files)
            )
            pipelined_write(
                files_in_order,
                rel.file_format,
                wanted,
                partitions,
                lineage,
                indexed,
                num_buckets,
                index_data_path,
                cfg,
            )
            return

        from ..telemetry.profiling import StageTimings, record_build_stages

        with StageTimings("build", "serial") as stages:
            with stages.timed("decode"):
                table = self._prepare_index_table(df, index_config)
            mesh = self._session.mesh_for(table.num_rows)
            if mesh is not None:
                # Cluster-wide build (the reference's repartition+bucketed-
                # write runs on the whole Spark cluster,
                # `CreateActionBase.scala:119-140`): rows ride an all_to_all
                # over the mesh; identical hash → identical index files. The
                # permutation comes back alone, and the pipeline's range
                # writer gathers the bucket files through it, its column
                # assembly overlapping the exchange.
                from ..parallel.table_ops import distributed_bucketize_perm
                from .build_pipeline import write_sorted

                stages.mode = "mesh"

                def sort():
                    with stages.timed("sort", rows=table.num_rows):
                        return distributed_bucketize_perm(
                            mesh, table, indexed, num_buckets, stages
                        )

                write_sorted([table], sort, index_data_path, cfg, stages)
                record_build_stages(
                    stages.summary(rows=table.num_rows, writers=cfg.writers)
                )
                return
            with stages.timed("sort", rows=table.num_rows):
                sorted_table, starts = bucketize_table(table, indexed, num_buckets)
            os.makedirs(index_data_path, exist_ok=True)
            import numpy as np
            from concurrent.futures import ThreadPoolExecutor

            def write_bucket(b: int) -> None:
                lo, hi = int(starts[b]), int(starts[b + 1])
                if hi <= lo:
                    return  # empty bucket: no file
                bucket_table = sorted_table.take(np.arange(lo, hi))
                # Bounded, key-sorted row groups (same bound as the pipelined
                # writer — the byte-identity contract includes the layout):
                # scan pushdown prunes inside bucket files through the footer
                # stats.
                engine_io.write_parquet(
                    bucket_table,
                    os.path.join(index_data_path, f"part-{b:05d}.parquet"),
                    row_group_rows=engine_io.index_row_group_rows(),
                )

            # Parquet encode is pyarrow C++ work that releases the GIL: writing
            # the bucket files concurrently keeps the build from serializing on
            # host I/O (SURVEY §7 — the executors of the reference's bucketed
            # write ran cluster-wide for the same reason).
            with stages.timed("write"):
                with ThreadPoolExecutor(max_workers=cfg.writers) as pool:
                    list(pool.map(write_bucket, range(num_buckets)))
            record_build_stages(stages.summary(rows=table.num_rows))

    def _mesh_may_apply(self, rel: SourceRelation) -> bool:
        """Whether the distributed mesh build could claim this source — decided
        BEFORE decoding (the pipeline wants to stream chunks, the mesh build
        wants the whole table). Parquet row counts come from the footers; for
        formats without cheap counts the answer is conservatively True, which
        routes to the legacy path where `mesh_for(table.num_rows)` decides
        exactly as before."""
        if not self._session.hs_conf.distributed_enabled:
            return False
        import jax

        if len(jax.devices()) < 2:
            return False
        if rel.file_format not in ("parquet", "delta"):
            return True
        try:
            import pyarrow.parquet as pq

            est = sum(pq.ParquetFile(f.path).metadata.num_rows for f in rel.files)
        except Exception:
            return True
        return self._session.mesh_for(est) is not None

    # -- metadata derivation (reference CreateActionBase.scala:41-117) ------

    def _index_schema(self, df: DataFrame, index_config: IndexConfig) -> Schema:
        indexed, included = self._resolved_columns(df, index_config)
        src = df.plan.output_schema
        fields: List[Field] = [src.field(n) for n in indexed + included]
        if self._session.hs_conf.lineage_enabled:
            for p in self._missing_partition_columns(df.plan.relation, indexed + included):
                fields.append(src.field(p))
            fields.append(Field(IndexConstants.DATA_FILE_NAME_COLUMN, STRING))
        return Schema(fields)

    def derive_log_entry(
        self, df: DataFrame, index_config: IndexConfig, index_path: str, index_data_path: str
    ) -> IndexLogEntry:
        rel = df.plan.relation
        provider = create_provider()
        sig = provider.signature(df.plan)
        if sig is None:
            raise HyperspaceException("Signature provider does not support this plan.")
        indexed, included = self._resolved_columns(df, index_config)

        relation = Relation(
            root_paths=list(rel.root_paths),
            data=Content(Directory.from_leaf_files("/", rel.files)),
            data_schema_json=rel.schema.to_json_string(),
            file_format=rel.file_format,
            options=dict(rel.options),
        )
        entry = IndexLogEntry(
            name=index_config.index_name,
            derived_dataset=CoveringIndexProperties(
                indexed_columns=indexed,
                included_columns=included,
                schema_json=self._index_schema(df, index_config).to_json_string(),
                num_buckets=self._session.hs_conf.num_buckets,
                properties={
                    IndexConstants.HASH_SCHEME_KEY: IndexConstants.HASH_SCHEME_VERSION
                },
            ),
            content=Content.from_directory(index_data_path, self._session.fs),
            source=Source(
                SourcePlanProperties(
                    relations=[relation],
                    fingerprint=LogicalPlanFingerprint(
                        signatures=[Signature(provider.name, sig)]
                    ),
                )
            ),
        )
        return entry

    # -- refresh support (reference RefreshAction.scala:44-56) --------------

    def reconstruct_df(self, relation: Relation) -> DataFrame:
        reader = self._session.read
        fmt = relation.file_format
        if fmt == "parquet":
            return reader.parquet(*relation.root_paths)
        if fmt == "csv":
            return reader.csv(*relation.root_paths)
        if fmt == "json":
            return reader.json(*relation.root_paths)
        if fmt == "orc":
            return reader.orc(*relation.root_paths)
        if fmt == "delta":
            return reader.delta(*relation.root_paths)
        raise HyperspaceException(f"Unsupported file format: {fmt}")

    def restrict_df_to_files(self, df: DataFrame, file_paths) -> DataFrame:
        """A view of the same relation limited to a subset of its files (used by
        incremental refresh to index only appended data)."""
        from ..engine.logical import ScanNode, SourceRelation
        from ..engine.session import DataFrame as DF

        rel = df.plan.relation
        wanted = set(file_paths)
        sub = SourceRelation(
            root_paths=list(rel.root_paths),
            file_format="parquet" if rel.file_format == "delta" else rel.file_format,
            schema=rel.schema,
            files=[f for f in rel.files if f.path in wanted],
            options=dict(rel.options),
            partition_spec=rel.partition_spec,
        )
        return DF(self._session, ScanNode(sub))

"""Session + DataFrame API.

The engine analogue of SparkSession + DataFrame, sized to what the reference's
workflows need: read parquet/csv/json into a lazily-planned DataFrame, filter/select/
join, collect on the TPU execution path. The session carries the conf, the filesystem,
and the optimizer extension point (`extra_optimizations`) that `enable_hyperspace`
plugs the rewrite rules into (the analogue of
`experimentalMethods.extraOptimizations`, reference `package.scala:46-51`).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Sequence, Union

from ..config import HyperspaceConf, SessionConf
from ..exceptions import HyperspaceException
from ..storage.filesystem import FileSystem, LocalFileSystem
from . import io as engine_io
from .expr import Expr
from .logical import (
    AggregateNode,
    FilterNode,
    JoinNode,
    LimitNode,
    LogicalPlan,
    OrderByNode,
    ProjectNode,
    ScanNode,
    SourceRelation,
    WithColumnNode,
)
from .physical import ExecContext, PhysicalNode, plan_physical
from .schema import Schema
from .table import Table


class DataFrame:
    def __init__(self, session: "HyperspaceSession", plan: LogicalPlan):
        self.session = session
        self.plan = plan

    # -- transformations ----------------------------------------------------

    def filter(self, condition: Expr) -> "DataFrame":
        return DataFrame(self.session, FilterNode(condition, self.plan))

    where = filter

    def select(self, *columns: str) -> "DataFrame":
        names = list(columns[0]) if len(columns) == 1 and isinstance(columns[0], (list, tuple)) else list(columns)
        missing = [n for n in names if n not in self.plan.output_schema]
        if missing:
            raise HyperspaceException(f"Column(s) not found: {missing}")
        return DataFrame(self.session, ProjectNode(names, self.plan))

    def join(self, other: "DataFrame", on: Expr, how: str = "inner") -> "DataFrame":
        return DataFrame(self.session, JoinNode(self.plan, other.plan, on, how))

    def with_column(self, name: str, expr: Expr) -> "DataFrame":
        """Computed column (Spark `withColumn`): replaces a same-named column in
        place, else appends. `df.with_column("revenue", col("price") * (1 - col("discount")))`."""
        return DataFrame(self.session, WithColumnNode(name, expr, self.plan))

    withColumn = with_column

    def group_by(self, *keys: str) -> "GroupedDataFrame":
        names = list(keys[0]) if len(keys) == 1 and isinstance(keys[0], (list, tuple)) else list(keys)
        for n in names:
            self.plan.output_schema.field(n)  # resolve-or-raise
        return GroupedDataFrame(self, names)

    groupBy = group_by

    def agg(self, **aggs) -> "DataFrame":
        """Global aggregation (no grouping): `df.agg(total=("qty", "sum"))`."""
        return GroupedDataFrame(self, []).agg(**aggs)

    def order_by(self, *keys, ascending: bool = True) -> "DataFrame":
        """ORDER BY. Keys are column names or (name, ascending) pairs; the
        `ascending` kwarg is the default for bare names."""
        parsed = []
        for k in keys:
            if isinstance(k, tuple):
                parsed.append((k[0], bool(k[1])))
            else:
                parsed.append((k, ascending))
        return DataFrame(self.session, OrderByNode(parsed, self.plan))

    orderBy = order_by
    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, LimitNode(n, self.plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        """Row union (UNION ALL) of same-schema frames — the same UnionNode the
        Hybrid Scan merge uses; use `.distinct()` after for set-union."""
        from .logical import UnionNode

        return DataFrame(self.session, UnionNode([self.plan, other.plan]))

    unionAll = union

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in BOTH frames (SQL INTERSECT; nulls compare
        equal to each other, like Spark's)."""
        from .logical import IntersectNode

        return DataFrame(self.session, IntersectNode(self.plan, other.plan))

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of this frame absent from `other` (SQL EXCEPT;
        Spark's `except`/`subtract`)."""
        from .logical import ExceptNode

        return DataFrame(self.session, ExceptNode(self.plan, other.plan))

    def scalar(self):
        """The single value of a 1x1 result — the scalar-subquery pattern
        (`WHERE x > (SELECT max(...) FROM ...)`) as eager composition:

            df.filter(col("x") > other.group_by().agg(m=("x", "max")).scalar())

        Raises unless the result is exactly one row x one column."""
        t = self.collect()
        if t.num_rows != 1 or len(t.column_names) != 1:
            raise HyperspaceException(
                f"scalar() requires a 1x1 result, got "
                f"{t.num_rows}x{len(t.column_names)}"
            )
        return t.rows()[0][0]

    def drop(self, *columns: str) -> "DataFrame":
        """Project away the named columns (missing names are ignored, like
        Spark's drop). Name matching honors `hyperspace.resolution.caseSensitive`
        like the planner does."""
        from ..util.resolver_utils import resolution_key

        cs = self.session.hs_conf.case_sensitive
        gone = {resolution_key(c, cs) for c in columns}
        keep = [
            n
            for n in self.plan.output_schema.names
            if resolution_key(n, cs) not in gone
        ]
        if not keep:
            raise HyperspaceException("drop() would remove every column")
        return self.select(keep)

    def distinct(self) -> "DataFrame":
        """Row dedup = GROUP BY every column with no aggregates (rides the same
        device hash-sort/segment kernel as aggregation)."""
        return DataFrame(
            self.session, AggregateNode(self.plan.output_schema.names, [], self.plan)
        )

    # -- actions ------------------------------------------------------------

    @property
    def schema(self):
        return self.plan.output_schema

    def optimized_plan(self) -> LogicalPlan:
        return self.session.optimize(self.plan)

    def physical_plan(self) -> PhysicalNode:
        return plan_physical(
            self.optimized_plan(),
            case_sensitive=self.session.hs_conf.case_sensitive,
        )

    def _run_with_quarantine_fallback(self, runner):
        """Plan + execute with the corruption-quarantine fallback: a
        `CorruptIndexError` (a truncated/corrupt index bucket file surfaced by
        the scan layer) QUARANTINES the named index, warns, and re-plans — the
        rules now skip the quarantined index (`rules.rule_utils`), so the
        retry executes against the source data and the result stays correct.
        Bounded by construction: every round quarantines a NEW index
        (`quarantine.mark` returns False on a repeat, which propagates)."""
        import time as _time
        import warnings

        from ..exceptions import CorruptIndexError
        from ..index import quarantine
        from ..plananalysis import attribution as _attribution
        from ..plananalysis import planner as _planner
        from ..telemetry import tracing

        while True:
            with tracing.span("plan"):
                phys = self.physical_plan()
            fp = self._attach_fingerprint(phys)
            decisions = _planner.decide(phys, fp)
            try:
                with _planner.decisions_scope(decisions):
                    pr0 = _planner.prune_counters()
                    t0 = _time.monotonic()
                    out = runner(phys)
                # Feed the measured wall back (outcome store; no-op without a
                # persistent home) — only on success: a quarantine retry's
                # partial wall would poison the arm stats. The row-group
                # pruning counter delta rides along so the class's pushdown
                # selectivity prior is learned, not guessed; so does the
                # per-stage wall snapshot (still-open query scope), which
                # lets the store learn at stage grain.
                _planner.observe(
                    decisions,
                    _time.monotonic() - t0,
                    pruning=_planner.prune_counters(pr0) if pr0 is not None else None,
                    stages=_attribution.query_stage_walls(),
                )
                return out
            except CorruptIndexError as e:
                if not quarantine.mark(e.index_name, reason=str(e), path=e.path):
                    raise
                warnings.warn(
                    f"hyperspace: index '{e.index_name}' quarantined after a "
                    f"corrupt data file; the query falls back to the source "
                    f"scan and stays correct ({e}). Refresh or rebuild the "
                    "index to lift the quarantine.",
                    RuntimeWarning,
                    stacklevel=3,
                )

    def _attach_fingerprint(self, phys: PhysicalNode):
        """Stamp the optimized plan's execution-class fingerprint
        (`plananalysis.fingerprint`) onto the ambient root span and ledger —
        the key the workload history store lands this query under — and
        return it (the adaptive planner's outcome store keys on the same
        class; only an OBSERVING planner — one with a persistent outcome
        home — counts as a consumer). Computed only when a consumer exists
        (history enabled / ledger open / span recording / planner learning);
        with everything off this is one env read + one contextvar read, the
        zero-cost-off contract."""
        from ..plananalysis import fingerprint as _fp
        from ..plananalysis import planner as _planner
        from ..telemetry import accounting, tracing

        try:
            if not _fp.fingerprint_wanted():
                if not (_planner.planner_enabled() and _planner.outcome_dir()):
                    return None
            fp = _fp.plan_fingerprint(phys)
        except Exception:
            return None  # fingerprinting must never fail the query
        accounting.set_value("plan_fingerprint", fp)
        sp = tracing.current_span()
        if sp is not None:
            sp.set_attr("plan_fingerprint", fp)
        return fp

    def collect(self) -> Table:
        from .. import resilience
        from ..telemetry import accounting, tracing

        with resilience.query_scope("query:collect"):
            with tracing.query_span("query:collect") as root:
                out = self._run_with_quarantine_fallback(
                    lambda phys: phys.execute(ExecContext(self.session))
                )
                root.set_attr("rows_out", int(out.num_rows))
                accounting.set_value("rows_produced", int(out.num_rows))
                return out

    def count(self) -> int:
        # Counts never assemble output they don't need: scans answer from parquet
        # footers, joins from verified pair counts (`PhysicalNode.execute_count`).
        from .. import resilience
        from ..telemetry import accounting, tracing

        with resilience.query_scope("query:count"):
            with tracing.query_span("query:count") as root:
                n = self._run_with_quarantine_fallback(
                    lambda phys: phys.execute_count(ExecContext(self.session))
                )
                root.set_attr("rows_out", int(n))
                accounting.set_value("rows_produced", int(n))
                return n

    def to_pydict(self) -> Dict[str, list]:
        return self.collect().to_pydict()

    def sorted_rows(self):
        return self.collect().sorted_rows()

    def explain_string(self) -> str:
        return self.physical_plan().tree_string()

    def explain(self, analyze: bool = False, redirect=None):
        """The physical plan tree; with ``analyze=True`` the query EXECUTES
        under a trace capture and the same tree comes back annotated with each
        node's measured wall time, rows out, cache/memo hits, stage spans
        (probe/verify/gather/…), and the optimizer-rule
        decisions that shaped it (`plananalysis.analyze`). Returns the string
        when `redirect` is None, else passes it to `redirect` (e.g. print)."""
        if analyze:
            from ..plananalysis.analyze import explain_analyze_string

            s = explain_analyze_string(self)
        else:
            s = self.explain_string()
        if redirect is not None:
            redirect(s)
            return None
        return s

    def show(self, n: int = 20, redirect=print) -> None:
        """Spark-style formatted preview of the first `n` rows."""
        t = self.limit(n + 1).collect()
        truncated = t.num_rows > n
        names = t.column_names
        cols = {c: t.column(c).decode_objects()[:n] for c in names}
        cells = [
            [("null" if v is None else str(v)) for v in cols[c]] for c in names
        ]
        widths = [
            max(len(name), *(len(x) for x in col), 0) if col else len(name)
            for name, col in zip(names, cells)
        ]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        def fmt(vals):
            return "|" + "|".join(f" {v:>{w}} " for v, w in zip(vals, widths)) + "|"
        lines = [sep, fmt(names), sep]
        for i in range(min(n, t.num_rows)):
            lines.append(fmt([cells[j][i] for j in range(len(names))]))
        lines.append(sep)
        if truncated:
            lines.append(f"only showing top {n} row{'s' if n != 1 else ''}")
        redirect("\n".join(lines))


class GroupedDataFrame:
    """`df.group_by(keys)` → aggregation builder (the Spark RelationalGroupedDataset
    analogue, over the SQL aggregates the engine executes on device:
    sum, count, count_distinct, min, max, avg)."""

    def __init__(self, df: DataFrame, keys: List[str]):
        self._df = df
        self._keys = keys

    def agg(self, **aggs) -> DataFrame:
        """`.agg(out_name=("column", "fn"), ...)` with
        fn ∈ sum|count|count_distinct|min|max|avg; `.agg(n=("*", "count"))` is
        count(*)."""
        if not aggs:
            raise HyperspaceException("agg() requires at least one aggregate")
        triples = []
        for out_name, spec in aggs.items():
            if not (isinstance(spec, tuple) and len(spec) == 2):
                raise HyperspaceException(
                    f"agg spec must be (column, fn): {out_name}={spec!r}"
                )
            col, fn = spec
            col = None if col in ("*", None) else col
            triples.append((out_name, fn.lower(), col))
        return DataFrame(
            self._df.session, AggregateNode(self._keys, triples, self._df.plan)
        )

    def count(self) -> DataFrame:
        """Spark-style `groupBy(...).count()` → a `count` column of group sizes."""
        return self.agg(count=("*", "count"))

    def sum(self, *cols: str) -> DataFrame:
        return self.agg(**{f"sum({c})": (c, "sum") for c in cols})

    def min(self, *cols: str) -> DataFrame:
        return self.agg(**{f"min({c})": (c, "min") for c in cols})

    def max(self, *cols: str) -> DataFrame:
        return self.agg(**{f"max({c})": (c, "max") for c in cols})

    def avg(self, *cols: str) -> DataFrame:
        return self.agg(**{f"avg({c})": (c, "avg") for c in cols})


class DataFrameReader:
    def __init__(self, session: "HyperspaceSession"):
        self._session = session

    def _read(self, paths, file_format: str) -> DataFrame:
        path_list = [paths] if isinstance(paths, str) else list(paths)
        files = []
        for p in path_list:
            files.extend(engine_io.list_data_files(p, file_format, self._session.fs))
        if not files:
            raise HyperspaceException(f"No {file_format} files found under {path_list}")
        schema = engine_io.infer_schema([f.path for f in files], file_format)
        # Absolute local paths throughout: partition discovery compares files
        # against roots, and relative spellings must not change the schema.
        # URL-scheme paths (s3://, memory://, ...) pass through untouched —
        # abspath would mangle them ("s3://x" -> "/cwd/s3:/x").
        import re

        def _abs(p: str) -> str:
            return p if re.match(r"^[A-Za-z][A-Za-z0-9+.-]*://", p) else os.path.abspath(p)

        roots = [_abs(p) for p in path_list]
        from ..storage.filesystem import FileStatus

        files = [
            FileStatus(_abs(f.path), f.size, f.modified_time, f.is_dir) for f in files
        ]
        # Hive layout: `key=value` path segments become columns appended to the
        # schema (the PartitioningAwareFileIndex analogue).
        from .partitioning import discover

        spec = discover(roots, [f.path for f in files])
        if spec is not None:
            clash = [c for c in spec.columns if c in schema]
            if clash:
                raise HyperspaceException(
                    f"Partition column(s) also present in data files: {clash}"
                )
            schema = Schema(list(schema.fields) + spec.fields)
        rel = SourceRelation(
            root_paths=roots,
            file_format=file_format,
            schema=schema,
            files=files,
            partition_spec=spec,
        )
        return DataFrame(self._session, ScanNode(rel))

    def parquet(self, *paths) -> DataFrame:
        return self._read(paths if len(paths) > 1 else paths[0], "parquet")

    def csv(self, *paths) -> DataFrame:
        return self._read(paths if len(paths) > 1 else paths[0], "csv")

    def json(self, *paths) -> DataFrame:
        return self._read(paths if len(paths) > 1 else paths[0], "json")

    def orc(self, *paths) -> DataFrame:
        return self._read(paths if len(paths) > 1 else paths[0], "orc")

    def view(self, name: str) -> DataFrame:
        """Read a named view registered with `session.create_view`."""
        return self._session.view(name)

    def delta(self, path: str) -> DataFrame:
        """Snapshot read of a delta-style transactional table (extension): the file
        set is resolved from the `_delta_log`, not a directory listing."""
        from ..storage import delta as delta_log

        files = delta_log.active_files(path, self._session.fs)
        if not files:
            raise HyperspaceException(f"Delta table has no active files: {path}")
        schema = engine_io.infer_schema([f.path for f in files], "delta")
        rel = SourceRelation(
            root_paths=[os.path.abspath(path)],
            file_format="delta",
            schema=schema,
            files=files,
        )
        return DataFrame(self._session, ScanNode(rel))


class HyperspaceSession:
    """One session = conf + filesystem + optimizer rules + warehouse location."""

    # Active-session context: thread-local first (the reference's
    # Hyperspace.getContext is per-thread, `Hyperspace.scala:108-120`), with
    # the last globally-created session as the cross-thread fallback.
    _active: Optional["HyperspaceSession"] = None
    _active_local = None  # threading.local, created lazily

    def __init__(
        self,
        warehouse: str = ".",
        conf: Optional[SessionConf] = None,
        fs: Optional[FileSystem] = None,
    ):
        self.warehouse = warehouse
        self.conf = conf or SessionConf()
        self.hs_conf = HyperspaceConf(self.conf)
        self.fs = fs or LocalFileSystem()
        # Rule protocol: rule.apply(plan, session) -> plan.
        self.extra_optimizations: List = []
        self._mesh = None
        self._views: Dict[str, LogicalPlan] = {}
        import threading

        if HyperspaceSession._active_local is None:
            HyperspaceSession._active_local = threading.local()
        HyperspaceSession._active_local.session = self
        HyperspaceSession._active = self

    @classmethod
    def active(cls) -> "HyperspaceSession":
        """The calling thread's most recent session, else the process-wide
        most recent one (the reference's thread-local getContext semantics
        with its global fallback)."""
        local = getattr(cls._active_local, "session", None) if cls._active_local else None
        if local is not None:
            return local
        if cls._active is None:
            raise HyperspaceException("No active HyperspaceSession.")
        return cls._active

    def mesh_for(self, num_rows: int):
        """The ambient device mesh, when distributed execution should handle this
        many rows — the engine analogue of Spark's ambient cluster. Returns None
        when disabled, below the row threshold, or on a single-device backend
        (where the exchange would be pure overhead)."""
        if not self.hs_conf.distributed_enabled:
            return None
        if num_rows < self.hs_conf.distributed_min_rows:
            return None
        import jax

        if len(jax.devices()) < 2:
            return None
        if self._mesh is None:
            from ..parallel.mesh import make_mesh

            self._mesh = make_mesh()
        return self._mesh

    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    # -- named views (the temp-view/catalog-table analogue) ------------------

    def create_view(self, name: str, df: DataFrame, replace: bool = True) -> None:
        """Register `df`'s logical plan under `name` (the createOrReplaceTempView
        analogue). Reading the view resolves to the underlying plan, so the
        index-rewrite rules see straight through it — the reference rewrites
        queries over views the same way
        (`E2EHyperspaceRulesTests.scala:221-247`).

        View NAMES are always case-insensitive (like Spark identifiers, whose
        caseSensitive conf governs column resolution, not table names) — a fixed
        rule, so toggling the conf can never strand a registered view."""
        key = name.lower()
        if not replace and key in self._views:
            raise HyperspaceException(f"View already exists: {name}")
        self._views[key] = df.plan

    def drop_view(self, name: str) -> bool:
        return self._views.pop(name.lower(), None) is not None

    def view(self, name: str) -> DataFrame:
        plan = self._views.get(name.lower())
        if plan is None:
            raise HyperspaceException(f"View not found: {name}")
        return DataFrame(self, plan)

    def optimize(self, plan: LogicalPlan) -> LogicalPlan:
        from ..telemetry import tracing
        from .logical import push_filters_below_computed

        plan = push_filters_below_computed(plan)
        for rule in self.extra_optimizations:
            # One span per rule application under the query's plan span; each
            # rule records its applied/skipped decisions onto it
            # (`rules.rule_utils.record_rule_decision`).
            with tracing.span(f"rule:{type(rule).__name__}"):
                plan = rule.apply(plan, self)
        return plan

    # -- data creation helpers (test/SampleData parity) ---------------------

    def create_table(self, data: Dict[str, list]) -> Table:
        return Table.from_pydict(data)

    def write_parquet(
        self,
        data: Union[Table, Dict[str, list]],
        path: str,
        row_group_rows: Optional[int] = None,
    ) -> None:
        """`row_group_rows` bounds the written parquet row groups (None =
        pyarrow default, one group for typical test sizes) — multi-row-group
        sources are what the scan pushdown's zone maps prune inside."""
        t = data if isinstance(data, Table) else Table.from_pydict(data)
        engine_io.write_parquet(
            t, os.path.join(path, "part-00000.parquet"), row_group_rows=row_group_rows
        )

    def write_orc(self, data: Union[Table, Dict[str, list]], path: str) -> None:
        t = data if isinstance(data, Table) else Table.from_pydict(data)
        engine_io.write_orc(t, os.path.join(path, "part-00000.orc"))

    def write_csv(self, data: Union[Table, Dict[str, list]], path: str) -> None:
        t = data if isinstance(data, Table) else Table.from_pydict(data)
        engine_io.write_csv(t, os.path.join(path, "part-00000.csv"))

    def write_json(self, data: Union[Table, Dict[str, list]], path: str) -> None:
        t = data if isinstance(data, Table) else Table.from_pydict(data)
        engine_io.write_json(t, os.path.join(path, "part-00000.json"))

    def write_delta(
        self, data: Union[Table, Dict[str, list]], path: str, mode: str = "append"
    ) -> None:
        from ..storage import delta as delta_log

        t = data if isinstance(data, Table) else Table.from_pydict(data)
        delta_log.write_delta(t, path, mode, self.fs)

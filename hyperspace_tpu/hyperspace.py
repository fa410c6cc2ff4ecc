"""The Hyperspace facade + session enable/disable implicits.

Parity: reference `Hyperspace.scala:24-133` (user-facing CRUD + explain; one manager
per session via a cached context) and `package.scala:34-74` (`enableHyperspace`
appends JoinIndexRule :: FilterIndexRule — join first, deliberately: join indexes
typically beat filter indexes — `disableHyperspace` removes them,
`isHyperspaceEnabled` checks).
"""

from __future__ import annotations

from typing import Optional

from .engine.session import DataFrame, HyperspaceSession
from .engine.table import Table
from .index.collection_manager import CachingIndexCollectionManager, IndexManager
from .index.index_config import IndexConfig
from .rules.data_skipping_rule import DataSkippingFilterRule
from .rules.filter_index_rule import FilterIndexRule
from .rules.join_index_rule import JoinIndexRule

_ALL_RULES = (JoinIndexRule, FilterIndexRule, DataSkippingFilterRule)

_MANAGER_ATTR = "_hyperspace_index_manager"


def _index_manager_for(session: HyperspaceSession) -> IndexManager:
    """Per-session cached manager (the reference's HyperspaceContext,
    `Hyperspace.scala:108-133`)."""
    mgr = getattr(session, _MANAGER_ATTR, None)
    if mgr is None:
        mgr = CachingIndexCollectionManager(session)
        setattr(session, _MANAGER_ATTR, mgr)
    return mgr


class Hyperspace:
    def __init__(self, session: Optional[HyperspaceSession] = None):
        self._session = session or HyperspaceSession.active()
        self._manager = _index_manager_for(self._session)

    # -- index CRUD (reference Hyperspace.scala:40-104) ---------------------

    def create_index(self, df: DataFrame, index_config: IndexConfig) -> None:
        from . import resilience
        from .telemetry import tracing

        with resilience.query_scope("build:create_index"):
            with tracing.query_span(
                "build:create_index", index_name=index_config.index_name
            ):
                self._manager.create(df, index_config)

    def delete_index(self, index_name: str) -> None:
        from . import resilience
        from .telemetry import tracing

        with resilience.query_scope("build:delete_index"):
            with tracing.query_span("build:delete_index", index_name=index_name):
                self._manager.delete(index_name)

    def restore_index(self, index_name: str) -> None:
        self._manager.restore(index_name)

    def vacuum_index(self, index_name: str) -> None:
        from . import resilience
        from .telemetry import tracing

        with resilience.query_scope("build:vacuum_index"):
            with tracing.query_span("build:vacuum_index", index_name=index_name):
                self._manager.vacuum(index_name)

    def refresh_index(self, index_name: str, mode: Optional[str] = None) -> None:
        """mode="full": rebuild from scratch (reference behavior).
        mode="incremental": index only appended source files, fold deletes
        through lineage (extension; docs/reliability.md "Live tables").
        mode="auto": incremental when its preconditions hold, full otherwise,
        no-op when already fresh.
        mode=None defers to ``HYPERSPACE_REFRESH_MODE`` (default "full").

        Runs as a BATCH-lane citizen: under a live `serve.QueryServer` the
        cooperative yield gate deprioritizes the refresh whenever interactive
        queries are pending, so refreshes never dent interactive p99."""
        import os

        from . import resilience
        from .telemetry import tracing

        mode = mode or os.environ.get("HYPERSPACE_REFRESH_MODE") or "full"
        with resilience.query_scope("build:refresh_index"):
            with resilience.lane_scope("batch"):
                with tracing.query_span(
                    "build:refresh_index", index_name=index_name, mode=mode
                ):
                    self._manager.refresh(index_name, mode)

    def optimize_index(self, index_name: str, mode: str = "quick") -> None:
        """Compact small per-bucket index files (extension; quick/full modes).
        Physically removes rows folded as deleted by incremental refreshes and
        clears the entry's delete set. Like refresh, runs as a BATCH-lane
        citizen under the serving scheduler's yield gate."""
        from . import resilience
        from .telemetry import tracing

        with resilience.query_scope("build:optimize_index"):
            with resilience.lane_scope("batch"):
                with tracing.query_span(
                    "build:optimize_index", index_name=index_name, mode=mode
                ):
                    self._manager.optimize(index_name, mode)

    def cancel(self, index_name: str) -> None:
        self._manager.cancel(index_name)

    def indexes(self) -> Table:
        return self._manager.indexes()

    def server(
        self,
        max_concurrent: Optional[int] = None,
        queue_depth: Optional[int] = None,
        tenant_budget: Optional[int] = None,
    ):
        """A multi-tenant `serve.QueryServer` front door over this session's
        engine process (docs/serving.md): bounded workers, priority lanes,
        per-tenant admission control, and single-flight shared caches.

            with hs.server() as srv:
                fut = srv.submit(lambda: df.collect(), tenant="alice",
                                 lane="interactive")

        ``HYPERSPACE_SERVING=0`` makes every submission execute inline and
        serially — the exact single-caller engine."""
        from .serve import QueryServer

        return QueryServer(
            max_concurrent=max_concurrent,
            queue_depth=queue_depth,
            tenant_budget=tenant_budget,
        )

    def explain(
        self,
        df: DataFrame,
        verbose: bool = False,
        redirect=None,
        analyze: bool = False,
    ) -> Optional[str]:
        """Plan diff with indexes on vs off (reference `Hyperspace.scala:101-104`).
        Prints unless `redirect` is given (a callable receiving the string).
        With ``analyze=True`` the query EXECUTES under a trace and the chosen
        plan renders annotated with measured wall times, row counts, cache
        hits, and the rule decisions that shaped it (`plananalysis.analyze`;
        same output as `df.explain(analyze=True)`)."""
        if analyze:
            from .plananalysis.analyze import explain_analyze_string

            s = explain_analyze_string(df)
        else:
            from .plananalysis.plan_analyzer import explain_string

            s = explain_string(df, self._session, self._manager.indexes(), verbose)
        if redirect is not None:
            redirect(s)
            return None
        print(s)
        return None


# ---------------------------------------------------------------------------
# Session implicits (reference package.scala:34-74)
# ---------------------------------------------------------------------------


def enable_hyperspace(session: HyperspaceSession) -> HyperspaceSession:
    """Plug the rewrite rules into the optimizer: JoinIndexRule first, then
    FilterIndexRule (ordering is deliberate, reference `package.scala:24-33`), then
    the data-skipping file-pruning rule (extension) for scans the covering rules
    left in place."""
    if not is_hyperspace_enabled(session):
        session.extra_optimizations = session.extra_optimizations + [
            JoinIndexRule(),
            FilterIndexRule(),
            DataSkippingFilterRule(),
        ]
    return session


def disable_hyperspace(session: HyperspaceSession) -> HyperspaceSession:
    session.extra_optimizations = [
        r for r in session.extra_optimizations if not isinstance(r, _ALL_RULES)
    ]
    return session


def is_hyperspace_enabled(session: HyperspaceSession) -> bool:
    return any(isinstance(r, _ALL_RULES) for r in session.extra_optimizations)

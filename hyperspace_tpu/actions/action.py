"""The generic lifecycle action: a two-phase commit on the operation log.

Parity: reference `actions/Action.scala:34-104` — `run()` = validate → begin → op → end,
where `begin()` writes log id base+1 with the transient state and `end()` writes id
base+2 with the final state, then deletes and recreates the `latestStable` pointer.
Telemetry events wrap the whole run; failures are logged and rethrown. Under a
traced action the two log writes are the spans `log:begin` and `log:commit`
and the body is `action:op`.
"""

from __future__ import annotations

import time
from typing import Optional

from ..exceptions import ConcurrentWriteError, HyperspaceException, LogCommitError
from ..index.log_entry import LogEntry
from ..index.log_manager import IndexLogManager
from ..telemetry import metrics as _metrics
from ..telemetry import tracing
from ..telemetry.event_logging import EventLogger, NoOpEventLogger
from ..telemetry.events import HyperspaceEvent
from . import states

_RECOVERED = _metrics.counter("index.recovered_transient")


def _recover_stable(
    log_manager: IndexLogManager, orphan: LogEntry, missing_ok: bool = False
) -> Optional[LogEntry]:
    """Resolve a dead writer's orphaned TRANSIENT latest entry to the latest
    STABLE one, so the next action proceeds instead of wedging on the corpse.

    `missing_ok` governs a log with NO stable entry at all (a killed FIRST
    create): create treats that as "nothing durable ever committed" and
    proceeds (returns None); refresh/optimize need a real prior entry and
    raise. Safe under a writer that is actually still alive: both writers
    share the same `base_id` (the orphan's id), so their next log writes
    contest the same id and the operation-log CAS lets exactly one win — the
    loser aborts cleanly with `ConcurrentWriteError`."""
    stable = log_manager.get_latest_stable_log()
    if stable is None and not missing_ok:
        raise HyperspaceException(
            f"Index log has only a transient entry (state {orphan.state}) and "
            "no stable state to recover to; run cancel() or vacuum."
        )
    _RECOVERED.inc()
    return stable


class Action:
    """Subclasses define: transient_state, final_state, validate(), op(), log_entry(),
    event() (reference's abstract members)."""

    def __init__(self, log_manager: IndexLogManager, event_logger: Optional[EventLogger] = None):
        self._log_manager = log_manager
        self._event_logger = event_logger or NoOpEventLogger()
        self._base_id: Optional[int] = None

    # -- abstract -----------------------------------------------------------

    @property
    def transient_state(self) -> str:
        raise NotImplementedError

    @property
    def final_state(self) -> str:
        raise NotImplementedError

    def validate(self) -> None:
        """Raise HyperspaceException if the action is not allowed in the current state."""

    def op(self) -> None:
        """The action body (Spark-job analogue: the TPU build for create/refresh)."""

    def log_entry(self) -> LogEntry:
        """The metadata record to commit at end()."""
        raise NotImplementedError

    def event(self, message: str) -> HyperspaceEvent:
        raise NotImplementedError

    # -- the FSM ------------------------------------------------------------

    @property
    def base_id(self) -> int:
        if self._base_id is None:
            latest = self._log_manager.get_latest_id()
            self._base_id = latest if latest is not None else -1
        return self._base_id

    def begin(self) -> None:
        """Write id base+1 with the transient state (reference `Action.scala:48-54`).
        An OCC conflict here means a concurrent writer won the race."""
        entry = self.log_entry()
        entry.state = self.transient_state
        entry.timestamp = int(time.time() * 1000)
        if not self._log_manager.write_log(self.base_id + 1, entry):
            # Classified OCC loss (subclass keeps the reference message for
            # existing callers matching on it).
            raise ConcurrentWriteError(
                "Another Index operation is in progress. Please retry."
            )

    def end(self) -> None:
        """Write id base+2 with the final state and refresh `latestStable`
        (reference `Action.scala:59-74`)."""
        entry = self.log_entry()
        entry.state = self.final_state
        entry.timestamp = int(time.time() * 1000)
        final_id = self.base_id + 2
        if not self._log_manager.write_log(final_id, entry):
            raise ConcurrentWriteError(
                "Another Index operation is in progress. Please retry."
            )
        if entry.state in states.STABLE_STATES:
            # The pointer writes used to return ignored bools (and the real
            # impl's failure mode is actually an fs exception): a failed
            # latestStable refresh silently left a STALE pointer that every
            # reader would then trust. Classified now — the numbered entry is
            # committed either way, so a failed pointer is recoverable (the
            # reader-side fallback scans ids descending), but the action must
            # report it rather than claim clean success. The CREATE decides
            # success: it replaces any existing pointer, so even a failed
            # delete is harmless once the create lands.
            try:
                self._log_manager.delete_latest_stable_log()
            except Exception:
                pass  # superseded by the create below, which overwrites
            try:
                created = self._log_manager.create_latest_stable_log(final_id)
            except Exception as e:
                raise LogCommitError(
                    f"Committed log id {final_id} but the latestStable "
                    f"pointer refresh failed ({type(e).__name__}: {e}); "
                    "readers fall back to the id scan until the next "
                    "successful action."
                ) from e
            if not created:
                raise LogCommitError(
                    f"Committed log id {final_id} but could not refresh the "
                    "latestStable pointer; readers fall back to the id scan "
                    "until the next successful action."
                )

    def run(self) -> None:
        """validate → begin → op → end, wrapped in telemetry (reference `:83-101`)."""
        self._event_logger.log_event(self.event("Operation Started."))
        try:
            self.validate()
            with tracing.span("log:begin"):
                self.begin()
            with tracing.span("action:op"):
                self.op()
            with tracing.span("log:commit"):
                self.end()
            self._event_logger.log_event(self.event("Operation Succeeded."))
        except Exception as e:  # log + rethrow (reference behavior)
            self._event_logger.log_event(self.event(f"Operation Failed: {e}"))
            raise

"""Crash-safe index-data commits: stage → single rename → operation-log CAS.

Builders previously wrote index files DIRECTLY into the final version
directory (`v__=N`); a process killed mid-build left a partial directory that
the next build's `Content.from_directory` inventory could pick up, and that
nothing ever reclaimed. This module makes the data commit atomic:

1. `stage_commit(final_path)` yields a STAGING directory (dot-prefixed, so the
   data-path filter, the version-id scan, and `Content.from_directory` all
   ignore it by construction) that the build writes into;
2. on success the staging dir is renamed to `final_path` in ONE `os.rename` —
   a SIGKILL before the rename leaves only an invisible staging dir, a SIGKILL
   after leaves a complete version dir that only becomes VISIBLE when the
   action's `end()` commits the log entry via the operation-log CAS;
3. a concurrent writer that already renamed `final_path` into place wins — the
   loser raises `ConcurrentWriteError` and deletes its staging dir (clean
   abort);
4. `reclaim_orphans(index_path)` deletes staging dirs whose creating process
   is dead (the pid rides the directory name), and runs at every action's
   manager resolution plus vacuum — killed builds are reclaimed by the next
   action on the index, exactly the "startup/vacuum reclaims" contract.

The staging dir lives in the same parent as `final_path` (same filesystem →
the rename is atomic on POSIX).
"""

from __future__ import annotations

import contextlib
import os
import shutil
import socket
import time
import uuid
from typing import Iterator, List, Optional, Tuple

from ..exceptions import ConcurrentWriteError
from ..telemetry import metrics as _metrics
from ..telemetry import tracing

#: Dot prefix: `util.path_utils.is_data_path` treats '.'-prefixed names as
#: metadata UNCONDITIONALLY (unlike '_'-prefixed, where '=' re-admits hive
#: partition dirs — and version dirs are named `v__=N`).
STAGING_PREFIX = ".staging-"

#: Reclamation age threshold for staging dirs from OTHER hosts (seconds):
#: pid liveness is only knowable for writers on THIS host, so a foreign
#: host's staging dir is reclaimed only once it has sat untouched this long —
#: a live cross-host build must never have its in-progress data deleted out
#: from under it (which would silently commit an index missing buckets).
ENV_STAGING_TTL_S = "HYPERSPACE_STAGING_TTL_S"
_DEFAULT_STAGING_TTL_S = 24 * 3600.0

_RECLAIMED = _metrics.counter("index.staging.reclaimed")
_COMMITS = _metrics.counter("index.staging.commits")
_ABORTS = _metrics.counter("index.staging.aborts")


def _staging_ttl_s() -> float:
    try:
        return max(
            0.0,
            float(os.environ.get(ENV_STAGING_TTL_S, "") or _DEFAULT_STAGING_TTL_S),
        )
    except ValueError:
        return _DEFAULT_STAGING_TTL_S


def _staging_name(final_name: str) -> str:
    # '~'-separated tail: hostnames may contain '-' and '.', so the owner
    # coordinates need a separator that cannot appear in them (or in the
    # `v__=N` final name).
    return (
        f"{STAGING_PREFIX}{final_name}"
        f"~{socket.gethostname()}~{os.getpid()}~{uuid.uuid4().hex[:8]}"
    )


def _owner_of(name: str) -> Tuple[Optional[str], int]:
    """(hostname, pid) encoded in a staging dir name; (None, -1) when
    unparseable (e.g. a dir from an older layout)."""
    parts = name.split("~")
    try:
        return parts[-3], int(parts[-2])
    except (IndexError, ValueError):
        return None, -1


def _pid_alive(pid: int) -> bool:
    from ..util.procs import pid_alive

    return pid_alive(pid)


@contextlib.contextmanager
def stage_commit(final_path: str) -> Iterator[str]:
    """Yield a staging directory for the build of `final_path`; commit it by
    rename on clean exit (the span `build:commit`), delete it on failure.
    Raises `ConcurrentWriteError` (after cleaning up) when `final_path`
    appeared concurrently."""
    final_path = final_path.rstrip(os.sep)
    parent = os.path.dirname(final_path) or "."
    os.makedirs(parent, exist_ok=True)
    stage = os.path.join(parent, _staging_name(os.path.basename(final_path)))
    try:
        yield stage
    except BaseException:
        _ABORTS.inc()
        shutil.rmtree(stage, ignore_errors=True)
        raise
    if not os.path.isdir(stage):
        # The build wrote nothing (e.g. a fake builder in FSM tests): nothing
        # to commit, and `Content.from_directory` of a missing final dir is
        # already the empty inventory.
        return
    with tracing.span("build:commit"):
        try:
            os.rename(stage, final_path)
        except OSError as e:
            _ABORTS.inc()
            shutil.rmtree(stage, ignore_errors=True)
            if os.path.exists(final_path):
                raise ConcurrentWriteError(
                    f"Another writer committed {final_path} first; this build "
                    "was aborted cleanly. Please retry."
                ) from e
            raise
    _COMMITS.inc()


def _is_orphan(path: str, name: str) -> bool:
    host, pid = _owner_of(name)
    if host == socket.gethostname() and pid > 0:
        # Our host: pid liveness is authoritative.
        return not _pid_alive(pid)
    # Another host (or an unparseable name): liveness is unknowable locally —
    # reclaim only once the dir has aged past the TTL, so a live cross-host
    # build keeps its in-progress staging area.
    try:
        age = time.time() - os.stat(path).st_mtime
    except OSError:
        return False  # vanished concurrently: someone else reclaimed it
    return age > _staging_ttl_s()


def list_orphans(index_path: str) -> List[str]:
    """Staging dirs under `index_path` whose writer is provably dead (same
    host, dead pid) or stale past `HYPERSPACE_STAGING_TTL_S` (foreign host)."""
    if not os.path.isdir(index_path):
        return []
    out = []
    for name in os.listdir(index_path):
        if not name.startswith(STAGING_PREFIX):
            continue
        if _is_orphan(os.path.join(index_path, name), name):
            out.append(os.path.join(index_path, name))
    return out


def reclaim_orphans(index_path: str) -> int:
    """Delete orphaned staging dirs under `index_path`; returns the count.
    Live writers are never touched (pid liveness on this host, TTL age for
    other hosts), so a concurrent build's staging area survives other
    actions racing on the same index."""
    n = 0
    for p in list_orphans(index_path):
        shutil.rmtree(p, ignore_errors=True)
        n += 1
    if n:
        _RECLAIMED.inc(n)
    return n

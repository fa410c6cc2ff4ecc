"""Device-resident encoded execution: stage dictionary CODES across the
host→device boundary, narrowed to the smallest integer width the dictionary
cardinality allows.

String columns are already dictionary-encoded on the host (int32 codes +
sorted `<U*` dictionary — `engine/table.py`), and PR 8 made the HOST half of
the pipeline code-space end to end. The DEVICE half still shipped the full
int32 code lane through every pow2 staging site and the mesh exchange's
padded send matrices. This module is the staging policy for the device half:

- `stage_codes(col, site)` — device-stage a string column's key lane as int8
  (dictionary ≤ 127 entries) or int16 (≤ 32767) instead of int32, through the
  identity-keyed upload cache, with the flat-vs-staged byte split recorded in
  the encoded-staging ledger. Non-qualifying columns stage flat, byte-for-byte
  as before.
- `narrow_codes(col)` — the memoized narrow copy (attached to the Column so
  the id-keyed upload cache keeps hitting across queries).
- `stage_aligned(arr, col, site)` — same policy for DERIVED code arrays (the
  union-dictionary-aligned verify lanes), width chosen from the array's own
  value range, identity-memoized.

Narrowing is value-preserving (codes < 2^width, the null code -1 survives any
signed width), so every consumer — hashing's `dh_table[codes]` gather, sort
operands, adjacent-equality group boundaries, pair-verification compares —
produces bit-identical results from narrow lanes; only the bytes over the
boundary shrink. Code width folds into the jit cache key the same way pow2
caps do: a BOUNDED {int8, int16, int32} class set per program, never a
per-cardinality shape (`tests/test_encoded_device.py` pins this).

Gate: `HYPERSPACE_ENCODED_DEVICE` — unset = auto (on when
`HYPERSPACE_ENCODED_EXEC` is on; per-column staging additionally wants the
column to have ridden an encoded read), `1` = force (every narrowable string
column qualifies), `0` = byte-identical flat-staging fallback in the standing
PR 1–12 oracle style.
"""

from __future__ import annotations

import os
import weakref

import numpy as np

ENV_ENCODED_DEVICE = "HYPERSPACE_ENCODED_DEVICE"

#: Narrow width policy: the null code -1 must survive, so widths are signed
#: and the dictionary must fit the POSITIVE range of the narrow type.
_INT8_MAX_CARD = 127
_INT16_MAX_CARD = 32767


def encoded_device_mode() -> str:
    """"off" | "force" | "auto" (the unset default)."""
    raw = os.environ.get(ENV_ENCODED_DEVICE)
    if raw is None or raw == "":
        return "auto"
    if raw == "0":
        return "off"
    return "force"


def encoded_device_enabled() -> bool:
    """Is the device-resident code path on at all? Auto defers to the master
    encoded-exec switch (`HYPERSPACE_ENCODED_EXEC`) — which, when unset,
    is itself decided per query by the adaptive planner
    (`plananalysis.planner`): one `encoded_exec` decision governs the host
    encoded layer, this device lane, and (transitively) packed code lanes."""
    mode = encoded_device_mode()
    if mode == "off":
        return False
    if mode == "force":
        return True
    from .encoding import encoded_exec_enabled

    return encoded_exec_enabled()


def code_dtype_for(cardinality: int):
    """Smallest signed dtype holding codes [-1, cardinality); None = int32
    already minimal (no narrowing to do)."""
    if cardinality <= _INT8_MAX_CARD:
        return np.int8
    if cardinality <= _INT16_MAX_CARD:
        return np.int16
    return None


def narrowable(col) -> bool:
    """Lane-level gate: may this column's code array travel narrow? Used by
    the mesh exchange and hash staging, where narrowing is provably
    value-identical — only the path-level switch and the width matter."""
    if not encoded_device_enabled():
        return False
    if not getattr(col, "is_string", False) or col.dictionary is None:
        return False
    return code_dtype_for(len(col.dictionary)) is not None


def column_qualifies(col) -> bool:
    """Per-column staging gate: `narrowable` plus, in auto mode, the column
    must have ridden an encoded read (`_encoded_read`, set by
    `encoding.dictionary_array_to_column` and propagated through take/concat)."""
    if not narrowable(col):
        return False
    if encoded_device_mode() == "force":
        return True
    return bool(getattr(col, "_encoded_read", False))


def narrow_codes(col) -> np.ndarray:
    """Narrow copy of a string column's code array, memoized on the Column so
    the identity-keyed upload cache keeps hitting across queries."""
    dt = code_dtype_for(len(col.dictionary))
    if dt is None or col.data.dtype != np.int32:
        return col.data
    cached = getattr(col, "_narrow_codes", None)
    if cached is not None and cached.dtype == dt and len(cached) == len(col.data):
        return cached
    narrow = col.data.astype(dt)
    try:
        col._narrow_codes = narrow
    except Exception:
        pass  # slotted/frozen column subclass: lose the memo, not the narrowing
    return narrow


def _charged_bytes(col, narrow: np.ndarray) -> int:
    """TRUE encoded footprint of a staged code lane: narrow codes + the
    dictionary + the validity lane — the same accounting
    `encoding.column_nbytes` charges the scan cache (the PR-8 fix)."""
    total = int(narrow.nbytes)
    if col.dictionary is not None:
        total += int(col.dictionary.nbytes)
    if col.validity is not None:
        total += int(col.validity.nbytes)
    return total


def widen_for_gather(codes):
    """Widen a narrow (or packed-then-unpacked) code lane to int32 before it
    INDEXES a pow2-padded table: the table's axis size (e.g. a 128-slot hash
    table for a 100-entry dictionary) can exceed the narrow index dtype's
    range. The cast runs on device — the wire already moved narrow/packed
    bytes. ONE home for the widen rule (`ops/hashing.py`, `ops/aggregate.py`,
    `engine/physical.py` gathers, and the packed tier all route here; the
    per-site ad-hoc casts this replaces were the PR 15 wart)."""
    import jax.numpy as jnp

    if codes.dtype != jnp.int32:
        return codes.astype(jnp.int32)
    return codes


def stage_codes(col, site: str):
    """Device-stage a column's key lane: bit-packed sub-byte words when the
    dictionary fits a packed class (`engine/packed_codes.py` — H2D moves
    `bits` bits per code, the device unpacks back to the narrow int8 lane),
    narrow codes when the column merely qualifies for encoded staging, flat
    data (byte-identical legacy path) otherwise."""
    from .device_cache import device_array

    if not column_qualifies(col):
        return device_array(col.data)
    from .packed_codes import packable_bits, stage_packed_codes

    bits = packable_bits(col)
    if bits is not None:
        return stage_packed_codes(col, site, bits)
    narrow = narrow_codes(col)
    if narrow is col.data:
        return device_array(col.data)
    return device_array(
        narrow,
        site=site,
        flat_bytes=int(col.data.nbytes),
        charged_bytes=_charged_bytes(col, narrow),
    )


# Derived code arrays (union-aligned verify lanes) are not Columns, so the
# narrow copies are memoized by array identity; entries die with their source
# arrays (which the two-table alignment cache owns).
_aligned_memo: dict = {}


def _narrow_array(arr: np.ndarray):
    """Narrow an int32 code array by its own value range (the union dictionary
    can exceed either side's), identity-memoized. Returns `arr` unchanged when
    int32 is already minimal."""
    key = id(arr)
    ent = _aligned_memo.get(key)
    if ent is not None and ent[0]() is arr:
        return ent[1]
    hi = int(arr.max(initial=0))
    dt = code_dtype_for(hi + 1)
    narrow = arr if dt is None else arr.astype(dt)
    try:
        ref = weakref.ref(arr, lambda _wr, k=key: _aligned_memo.pop(k, None))
    except TypeError:
        return narrow
    _aligned_memo[key] = (ref, narrow)
    return narrow


def stage_aligned(arr: np.ndarray, col, site: str):
    """Device-stage a derived int32 code array (e.g. union-aligned codes) for
    a qualifying source column; flat staging otherwise."""
    from .device_cache import device_array

    if (
        not isinstance(arr, np.ndarray)
        or arr.dtype != np.int32
        or not column_qualifies(col)
    ):
        return device_array(arr)
    narrow = _narrow_array(arr)
    if narrow is arr:
        return device_array(arr)
    charged = int(narrow.nbytes)
    if col.dictionary is not None:
        charged += int(col.dictionary.nbytes)
    return device_array(
        narrow, site=site, flat_bytes=int(arr.nbytes), charged_bytes=charged
    )


def record_mesh_wire_stage(n_rows: int, flat_lanes, lanes, packed_spec=None) -> None:
    """Encoded-vs-flat ledger entry (site ``mesh_exchange``) for one mesh
    exchange's wire lanes: what full-width lanes (`flat_lanes` dtypes) would
    have staged vs what the narrow `lanes` stage. Lanes marked in
    `packed_spec` (aligned with `lanes`) cross bit-packed — their staged cost
    is true bits-on-the-wire, surfaced in the ledger's `packed` tier. The
    exchange ships the same lanes whatever this layer's switch says; the
    entry is booked only while the layer is on, as its other sites are."""
    if not encoded_device_enabled():
        return
    from ..telemetry import device_observatory as _devobs

    flat = sum(n_rows * int(np.dtype(d).itemsize) for d in flat_lanes)
    staged = 0
    packed = 0
    for i, a in enumerate(lanes):
        bits = packed_spec[i][0] if packed_spec is not None else 0
        if bits:
            lane_bytes = -(-n_rows * bits // 8)  # ceil(n_rows*bits/8)
            packed += lane_bytes
        else:
            lane_bytes = n_rows * int(a.dtype.itemsize)
        staged += lane_bytes
    _devobs.record_encoded_stage(
        "mesh_exchange", flat, staged, packed_bytes=packed if packed else None
    )

"""Arithmetic shared by the per-layer metrics' readers (`metrics/<name>.py`).

Each reader gets the traced run's `Readings` (run.py): the run and its
records, the program's counters over the window, and the reduced device trace
with the program's spans on its clock. A reader that finds nothing to read returns None.
"""

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def peak(device_kind: str, key: str) -> float:
    """A published peak of the chip; a chip not in `peaks.json` is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in peaks.json")
    return float(peaks[device_kind][key])


def roofline_pct(ctx, device_s: float, bytes_needed: float):
    """The least time the bytes need at the HBM peak, over `device_s`, the
    device time the stage took, in percent; None when the stage did not run."""
    if device_s <= 0 or bytes_needed <= 0:
        return None
    return 100.0 * bytes_needed / peak(ctx.device_kind, "hbm_bytes_per_s") / device_s

"""Kernels: the merge's share of its roofline, in %. The least time the chip
could take is the bytes any implementation of the merge must move (input rows
x (8-byte key + 8-byte sequence number + 4-byte output position), reckoned
from the table and not from the program) over the HBM peak of the device
kind; it is divided by the seconds the devices were busy. Bandwidth bounds
it: a merge compares and moves, it does not multiply. A device kind that is
not in peaks.json is an error; no busy time, nothing to read."""

import reference


def read(w):
    busy = sum(w.busy_s.values())
    if busy <= 0 or not w.rows:
        return None
    peak = w.peaks[w.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (w.rows * reference.MERGE_BYTES_PER_ROW / peak) / busy

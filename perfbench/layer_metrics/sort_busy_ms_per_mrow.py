"""Kernels: device time of the window in the sort instructions of the merge
programs (`jit_dedup_select*`, `jit_merge_plan*`, ...: the names
paimon_tpu/ops/merge.py gives its jitted functions), per million input rows.
With `nonsort_busy_ms_per_mrow` it is `kernel_busy_ms_per_mrow`."""

from program_spans import sort_busy_s


def read(w):
    sort = sort_busy_s(w)
    return sort * 1e3 / (w.rows / 1e6) if sort is not None and w.rows else None

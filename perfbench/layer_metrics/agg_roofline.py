"""Kernels: the aggregation merge's share of its roofline, in %. The least
time the chip could take is the bytes any aggregation merge must move
(`reference_aggregate.merge_bytes`: the input rows' keys, sequence numbers,
row kinds and validity bits, and an 8-byte result per field and key) for the
merges the window ran, as the program's `aggregate{...}` counters give them:
`rows_in` the input rows, `rows_out` the keys, `fields` / `merges` the value
fields of a merge. It is divided by the HBM peak of the device kind, then by
the seconds the devices were busy in the aggregation's programs: the plan
(`jit_merge_plan*`), the per-field reductions (`jit_agg_*`) and the fused
kernel (`jit_fused_aggregate`). 0 where none of them ran; None where they ran
and the window counts no aggregation merge (a program without the
counters)."""

import reference_aggregate as ref
from program_spans import counter_delta
from trace_reader import union_seconds

PROGRAMS = ("jit_merge_plan", "jit_agg_", "jit_fused_aggregate")


def _busy_s(w) -> float:
    total = 0.0
    for ops in w.trace.devices.values():
        total += union_seconds([(s, e) for s, e, n in ops if n.split("/", 1)[0].startswith(PROGRAMS)])
    return total


def read(w):
    busy = _busy_s(w)
    if busy <= 0:
        return 0.0
    merges = counter_delta(w, "aggregate", "merges")
    if not merges:
        return None
    rows_in, keys = counter_delta(w, "aggregate", "rows_in"), counter_delta(w, "aggregate", "rows_out")
    fields = counter_delta(w, "aggregate", "fields") / merges
    peak = w.peaks[w.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (ref.merge_bytes(rows_in, keys, fields) / peak) / busy

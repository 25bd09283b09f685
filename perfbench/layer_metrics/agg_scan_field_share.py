"""Aggregation path: `aggregate{fields_by_scan}` / `aggregate{fields}` over
the window: the share of the value fields that the aggregation merges reduced
by scans read at the segments' bounds (integer sums, counts, first/last
picks, primary-key) and not by a scatter or on the host. 0 where the window
merged fields and counted none by scan (a program without the counter: the
parent of the scans), and 0 where it merged nothing by aggregation; nothing
to read where the device ran the aggregation's programs (`jit_merge_plan*`,
`jit_agg_*`, `jit_fused_aggregate`) and the window counts no aggregation
merge (a program without the `aggregate{...}` counters)."""

from program_spans import counter_delta

PROGRAMS = ("jit_merge_plan", "jit_agg_", "jit_fused_aggregate")


def read(w):
    fields = counter_delta(w, "aggregate", "fields")
    if fields:
        return counter_delta(w, "aggregate", "fields_by_scan") / fields
    ran = any(n.split("/", 1)[0].startswith(PROGRAMS) for ops in w.trace.devices.values() for _, _, n in ops)
    return None if ran else 0.0

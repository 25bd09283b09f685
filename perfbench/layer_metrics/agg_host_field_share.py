"""Aggregation path: `aggregate{fields_on_host}` / `aggregate{fields}` over
the window: the share of the value fields that the aggregation merges reduced
on the host (f64 sum/max/min where the backend has no exact f64, product and
the collecting functions) and not on the device. 0 where the window merged
nothing by aggregation; nothing to read where the device ran the
aggregation's programs (`jit_merge_plan*`, `jit_agg_*`,
`jit_fused_aggregate`) and the window counts no aggregation merge (a program
without the counters)."""

from program_spans import counter_delta

PROGRAMS = ("jit_merge_plan", "jit_agg_", "jit_fused_aggregate")


def read(w):
    fields = counter_delta(w, "aggregate", "fields")
    if fields:
        return counter_delta(w, "aggregate", "fields_on_host") / fields
    ran = any(n.split("/", 1)[0].startswith(PROGRAMS) for ops in w.trace.devices.values() for _, _, n in ops)
    return None if ran else 0.0

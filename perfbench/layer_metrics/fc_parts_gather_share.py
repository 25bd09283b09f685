"""Write path: the cells of a rewrite's winners taken straight from the
per-file parts over all cells gathered, `compaction{rows_gathered_from_parts}`
/ `compaction{rows_gathered}` over the window: how often a dedup rewrite's
gather from parts engages (a numpy-valued or code-backed value column; not
an arrow-backed one, nor the key columns, seq and kind, nor a section whose
keys already strictly increase, nor the whole-batch engines). 0 where the
window rewrote nothing, and in a process that never rewrote; nothing to read
on a program that rewrites (`compaction{rows_in}`) and counts no
`compaction{rows_gathered}`, as programs before PR 39 do."""

from program_spans import counter_delta


def read(w):
    group = w.counters_after.get("compaction", {})
    if "rows_in" in group and "rows_gathered" not in group:
        return None
    gathered = counter_delta(w, "compaction", "rows_gathered")
    return (counter_delta(w, "compaction", "rows_gathered_from_parts") or 0) / gathered if gathered else 0.0

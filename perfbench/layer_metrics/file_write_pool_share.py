"""Write path: data files written as tasks of the shared pool over all data
files written, `datafile{files_written_on_pool}` / `datafile{files_written}`
over the window: how often a write's files go side by side. A write of one
file, and one called from a pool thread, counts in the base only. 0 where the
window wrote no file; nothing to read on a program that counts no file
written."""

from program_spans import counter_delta


def read(w):
    if "files_written" not in w.counters_after.get("datafile", {}):
        return None
    written = counter_delta(w, "datafile", "files_written")
    return counter_delta(w, "datafile", "files_written_on_pool") / written if written else 0.0

"""Write path: per client operation, the `files.write` spans (the calling
thread's fan-out of one write's files over the shared pool and its wait for
them; opened only where a write is cut into more than one file). The wall of
the files written side by side, where `file_write_ms_p50` and
`fc_file_write_ms_p50` sum the `file.write` spans of every thread: their busy
time. Median over the window's operations; 0 where most operations write one
file a call, and on a program that opens no such span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "files.write")

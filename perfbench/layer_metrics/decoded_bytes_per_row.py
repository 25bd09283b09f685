"""File decode: bytes of decoded batches (`datafile{bytes_decoded}`) per record
read (`read{rows_in}`) over the window."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "datafile", "bytes_decoded", "read", "rows_in")

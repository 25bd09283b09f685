"""Mesh: median over the window's operations of the reading thread's wait for the
split feeder, the program's `mesh.feed` spans and the `pipeline.scan.wait` they
hold: file decode, concat and key lanes run on the feeder's threads, and this
is how long the reader, and with it the chips, waited for them. 0 where the
mesh engine was not used."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "mesh.feed", "pipeline.scan.wait")

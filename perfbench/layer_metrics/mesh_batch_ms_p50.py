"""Mesh: median over the window's operations of the reading thread's time in
`mesh.batch` and the spans inside it, an operation's calls summed: `mesh.h2d`
(the upload enqueued), `mesh.run` (the `shard_map` program enqueued) and
`mesh.d2h` (blocked until the program is done, then the download). 0 where the
mesh engine was not used."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "mesh.batch", "mesh.h2d", "mesh.run", "mesh.d2h")

"""Mesh: `shard_map` calls an operation makes, `mesh{shards}` / `read{ops}` over
the window. The reader's plan decides it (ceil(data splits / bucket axis) for
splits of one merge family), so it is a whole number and the same in every run;
0 where the mesh engine was not used."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "mesh", "shards", "read", "ops")

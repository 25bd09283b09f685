"""Mesh: median over the window's operations of the reading thread's self time in
`mesh.plan` (one lane plan over a batch's shards, applied to each) and
`mesh.stack` (the padded (shards, rows, lanes) arrays a `shard_map` call
uploads), an operation's batches summed. 0 where the mesh engine was not used."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "mesh.plan", "mesh.stack")

"""Write path: part of the client thread's time inside the window's operations
(`pb:op`) that lies under no `pt:` span naming its work (any but the containers
`prepare_commit`, `flush`, `compact`): what the program has not named. On a
program without the write path's spans it reads 1."""

from ingest_spans import unattributed_share


def read(w):
    return unattributed_share(w)

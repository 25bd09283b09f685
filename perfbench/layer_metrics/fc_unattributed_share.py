"""Write path, full-compaction cell: part of the client thread's time inside
the window's operations (`pb:op`) that lies under no `pt:` span naming its
work (any but the containers of `ingest_spans`), with the benchmark's own
`pb:clone` taken out of the operations first: what the program has not named
of a dedicated job's round. On a program without the write path's spans it
reads near 1; None where the trace holds no `pb:op`."""

from ingest_spans import CONTAINERS, load
from program_spans import overlap
from trace_reader import union_seconds


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def read(w):
    spans = load(w.trace.path)
    ops = [(s, e) for s, e, _ in spans.ops]
    if not ops:
        return None
    lines = {line for _, _, line in spans.ops}
    clones = _merged(w.trace.spans.get("clone", []))
    named = _merged([(s.start, s.end) for s in spans.spans if s.line in lines and s.name not in CONTAINERS] + clones)
    total = union_seconds(ops) - overlap(ops, clones)
    return 1.0 - (overlap(ops, named) - overlap(ops, clones)) / total if total > 0 else None

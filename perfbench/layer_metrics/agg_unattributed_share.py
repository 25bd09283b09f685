"""Aggregation path: part of the window's operations (`pb:op`, the plan
included) that lies on the operation's thread under no `pt:` span but the
containers `read_all` and `split` (`program_spans.CONTAINERS`): what the
program has not named of a whole read of an aggregation table. None where the
trace holds no `pb:op`."""

import ingest_spans
from program_spans import CONTAINERS, overlap


def read(w):
    spans = ingest_spans.load(w.trace.path)
    ops = [(s, e) for s, e, _ in spans.ops]
    total = sum(e - s for s, e in ops)
    if total <= 0:
        return None
    lines = {line for _, _, line in spans.ops}
    named: list[list[float]] = []
    for s, e in sorted((s.start, s.end) for s in spans.spans if s.line in lines and s.name not in CONTAINERS):
        if named and s <= named[-1][1]:
            named[-1][1] = max(named[-1][1], e)
        else:
            named.append([s, e])
    return 1.0 - overlap(ops, [(s, e) for s, e in named]) / total

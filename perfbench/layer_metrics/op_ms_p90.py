"""Table API: nearest-rank 90th percentile of the wall time of one whole
client operation in the window, from the benchmark's own span. Nothing to
read under 20 operations: with fewer it would be the maximum or nearly so.
(Not an end-to-end metric: in the 1M-row cell about every eleventh operation
is a quarter slower, so the 90th percentile sits on the edge between the two
kinds and swings between them from run to run; PERF.md section 2.)"""

import math


def read(w):
    ops = sorted(w.span_s("op"))
    if len(ops) < 20:
        return None
    return ops[math.ceil(len(ops) * 0.90) - 1] * 1e3

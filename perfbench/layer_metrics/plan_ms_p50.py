"""Planning: median wall time of new_scan().plan(), from the benchmark's span
around it."""

import statistics


def read(w):
    plans = w.span_s("plan")
    return statistics.median(plans) * 1e3 if plans else None

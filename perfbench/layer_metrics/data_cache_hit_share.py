"""File decode: hits / (hits + misses) of the program's data-file cache over
the window, from its own `cache` metrics group. It says whether the cell
decodes at all. Nothing to read where the cache was never asked."""

GROUP = "cache{'cache': 'data-file'}"


def read(w):
    before, after = w.counters_before.get(GROUP, {}), w.counters_after.get(GROUP, {})
    hits = after.get("hits", 0) - before.get("hits", 0)
    misses = after.get("misses", 0) - before.get("misses", 0)
    return hits / (hits + misses) if hits + misses > 0 else None

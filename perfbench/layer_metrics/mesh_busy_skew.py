"""Mesh: the busiest device's busy time in the window over the mean of the
devices' (union of the device-op intervals of the trace, a device): 1.0 where
the shards keep the chips equally busy, which is also what one device reads;
`chips` where one chip does all the work. Nothing to read where no device ran
anything."""


def read(w):
    busy = list(w.busy_s.values())
    total = sum(busy)
    return max(busy) * len(busy) / total if total > 0 else None

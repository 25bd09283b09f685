"""Device: 1 - busy time / window, of the busiest device, from the trace."""


def read(w):
    if not w.busy_s:
        return None
    return 1.0 - max(w.busy_s.values()) / w.trace.window_s

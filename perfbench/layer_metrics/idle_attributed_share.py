"""Device: part of the busiest device's idle time in the window that lies under
a `pt:` span of the reading thread that names its work (any but the containers
`read_all` and `split`): how much of the wait the program's spans explain."""

from program_spans import busiest_device, load


def read(w):
    device = busiest_device(w)
    return load(w.trace.path).idle_attributed_share(*device) if device else None

"""Merge dispatch: seconds of backend_compile_duration inside the window."""


def read(w):
    return w.programs_after["compile_s"] - w.programs_before["compile_s"]

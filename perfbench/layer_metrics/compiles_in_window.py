"""Merge dispatch: XLA programs requested inside the window, compiled or
loaded from the persistent cache (jax.monitoring). Should read 0."""


def read(w):
    return w.programs_after["requests"] - w.programs_before["requests"]

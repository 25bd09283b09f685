"""Top of run.py to t0: imports, the device check, the compile cache, the
table written from the seed, os.sync(), the warm-up operations."""


def read(w):
    return w.setup_s

"""perfbench's modules are files beside run.py, not a package: put them and
the checkout on the path, and pin the device kernels as the repo's own tests
do (on a CPU-only backend the program adapts to numpy's lexsort)."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("PAIMON_TPU_FORCE_DEVICE_ENGINE", "1")

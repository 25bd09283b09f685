#!/usr/bin/env python
"""Scale proof for the key-range tiled merge (VERDICT r2 #10): a section far
larger than one device dispatch should stream through deduplicate_select_tiled
with correctness intact and throughput roughly flat across tile sizes (the
async per-tile dispatch overlaps host slicing with device sorts).

The reference handles over-memory sections by spilling (MergeSorter.java:
110-116); here the key space is cut on the most significant lane so every
duplicate lands in exactly one tile — no spill files, no re-merge pass.

Emits one JSON line per (rows, tile_rows) cell + a correctness line.
Usage: python benchmarks/tiled_scale.py [--rows 16777216] [--tiles 1048576,4194304,16777216]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paimon_tpu.utils import enable_compile_cache, require_device

enable_compile_cache()
PLATFORM, DEVICE_KIND, DEVICE_COUNT = require_device()

BASE = 975_400.0


def emit(metric, value, unit="rows/s", **extra):
    print(
        json.dumps(
            {"metric": metric, "value": round(value, 1), "unit": unit,
             "vs_baseline": round(value / BASE, 3) if unit == "rows/s" else None,
             "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT, **extra}
        ),
        flush=True,
    )


def make_runs(n: int, n_runs: int = 4, dup: int = 4, seed: int = 11):
    """n rows as n_runs key-sorted runs (ascending seq across runs), the
    shape deduplicate_select_tiled expects."""
    rng = np.random.default_rng(seed)
    n -= n % n_runs  # runs must tile the input exactly (no orphan rows)
    keys = rng.integers(0, max(n // dup, 1), size=n, dtype=np.uint32)
    per = n // n_runs
    lanes = np.empty((n, 1), dtype=np.uint32)
    offsets = [0]
    for r in range(n_runs):
        chunk = np.sort(keys[r * per : (r + 1) * per])
        lanes[r * per : (r + 1) * per, 0] = chunk
        offsets.append((r + 1) * per)
    return lanes, offsets


def oracle(lanes: np.ndarray, offsets) -> np.ndarray:
    """Numpy ground truth: per key, the LAST occurrence in run order (runs
    are seq-ascending, stability ties to input order)."""
    keys = lanes[:, 0]
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    keep_last = np.concatenate([sk[1:] != sk[:-1], [True]])
    return order[keep_last]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16 * 1024 * 1024)
    ap.add_argument("--tiles", default="1048576,4194304,16777216")
    args = ap.parse_args()

    from paimon_tpu.ops.merge import deduplicate_select_tiled

    lanes, offsets = make_runs(args.rows)
    args.rows = offsets[-1]  # rounded to a run multiple
    expect = np.sort(oracle(lanes, offsets))

    for tile in (int(x) for x in args.tiles.split(",")):
        t0 = time.perf_counter()
        got = deduplicate_select_tiled(lanes, offsets, tile_rows=tile)
        dt = time.perf_counter() - t0
        ok = np.array_equal(np.sort(np.asarray(got)), expect)
        emit(
            f"tiled-dedup.tile{tile}", args.rows / dt, rows=args.rows,
            tile_rows=tile, selected=int(len(got)), correct=bool(ok),
        )
        if not ok:
            emit("tiled-dedup.MISMATCH", 0.0, unit="flag", tile_rows=tile)
            sys.exit(2)
    emit("tiled-dedup.correctness", 1.0, unit="flag", rows=args.rows)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Per-stage time decomposition of the merge-read hot path.

Answers "where does the time go" for the headline benchmark (bench.py
config): host columnar decode, key-lane encode, host->device transfer,
device sort+select kernel, winner gather. The kernel stage is isolated by
dispatching with pre-staged device arrays; the transfer stage is the delta
between dispatch-from-host and dispatch-from-device. Prints one JSON line
per stage plus the reconstructed total.

Usage: python benchmarks/decompose.py [--rows N] [--runs K]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paimon_tpu.utils import enable_compile_cache, require_device

enable_compile_cache()
PLATFORM, DEVICE_KIND, DEVICE_COUNT = require_device()


def best_of(fn, iters=3):
    best = float("inf")
    for i in range(iters + 1):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        if i > 0:  # first run warms caches
            best = min(best, dt)
    return best, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--runs", type=int, default=4)
    args = ap.parse_args()

    import jax

    from benchmarks.micro_benchmarks import make_table  # noqa: F401  (path setup)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__))))
    from micro_benchmarks import make_table

    import jax.numpy as jnp

    from paimon_tpu.data.keys import encode_key_lanes
    from paimon_tpu.ops.merge import (
        _dedup_select_fn,
        deduplicate_resolve,
        drop_constant_lanes,
        pad_size,
    )

    tmp = tempfile.mkdtemp(prefix="ptb_decomp_")
    results = {}
    try:
        t, _ = make_table(tmp, "parquet", args.rows, runs=args.runs, write_only=True)
        store = t.store
        plan = store.new_scan().plan()
        files = [e.file for e in plan.entries]
        rf = store.reader_factory((), 0)

        # --- stage 1: host columnar decode (all columns) -------------------
        def decode():
            return [rf.read(f) for f in files]

        results["decode_ms"], batches = best_of(decode)
        from paimon_tpu.core.kv import KVBatch

        kv = KVBatch.concat(batches)

        # --- stage 2: key-lane encode --------------------------------------
        def encode():
            return encode_key_lanes(kv.data, ["id"], {})

        results["lane_encode_ms"], lanes = best_of(encode)
        kl = drop_constant_lanes(lanes)
        if kl.shape[1] == 0:
            kl = lanes[:, :1]
        n, k = kl.shape
        m = pad_size(n)
        klp = np.full((k, m), 0xFFFFFFFF, dtype=np.uint32)
        klp[:, :n] = kl.T
        slp = np.zeros((0, m), dtype=np.uint32)
        pad = np.zeros(m, dtype=np.uint32)
        pad[n:] = 1
        fn = _dedup_select_fn(k, 0)

        # --- stage 3: kernel from host arrays (includes upload) ------------
        def kernel_from_host():
            packed, count = fn(klp, slp, pad)
            return deduplicate_resolve((packed, count))

        results["kernel_plus_transfer_ms"], take = best_of(kernel_from_host)

        # --- stage 4: kernel with pre-staged device arrays (no upload) -----
        dklp, dslp, dpad = jnp.asarray(klp), jnp.asarray(slp), jnp.asarray(pad)

        def kernel_device_only():
            packed, count = fn(dklp, dslp, dpad)
            return deduplicate_resolve((packed, count))

        results["kernel_ms"], _ = best_of(kernel_device_only)
        results["transfer_ms"] = max(results["kernel_plus_transfer_ms"] - results["kernel_ms"], 0.0)

        # --- stage 5: winner gather on host --------------------------------
        def gather():
            return kv.take(take)

        results["gather_ms"], merged = best_of(gather)

        total = (
            results["decode_ms"]
            + results["lane_encode_ms"]
            + results["kernel_plus_transfer_ms"]
            + results["gather_ms"]
        )
        meta = {
            "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT,
            "rows": args.rows,
            "runs": args.runs,
            "merged_rows": merged.num_rows,
            "lane_bytes": int(klp.nbytes + pad.nbytes),
        }
        for stage in ("decode_ms", "lane_encode_ms", "transfer_ms", "kernel_ms", "gather_ms"):
            print(
                json.dumps(
                    {
                        "metric": f"merge-read.stage.{stage[:-3]}",
                        "value": round(results[stage] * 1000, 2),
                        "unit": "ms",
                        "share": round(results[stage] / total, 3),
                    }
                ),
                flush=True,
            )
        print(
            json.dumps(
                {
                    "metric": "merge-read.stage.total",
                    "value": round(total * 1000, 2),
                    "unit": "ms",
                    "rows_per_s": round(args.rows / total, 1),
                    **meta,
                }
            ),
            flush=True,
        )

        # --- the ADAPTIVE HOST pipeline (what table reads actually run on a
        # CPU-only backend, mergefn.effective_sort_engine): keys-only decode
        # without _SEQUENCE_NUMBER, host lexsort dedup, value-column decode,
        # winner gather ----------------------------------------------------
        host = {}
        key_cols = ["id"]
        rest = [n for n in t.row_type.field_names if n not in key_cols]

        def h_decode_keys():
            return [rf.read(f, fields=key_cols, system_columns="kind") for f in files]

        host["decode_keys_ms"], heads = best_of(h_decode_keys)
        kvk = KVBatch.concat(heads)

        def h_sort():
            from paimon_tpu.core.mergefn import _numpy_dedup_select

            lanes2 = encode_key_lanes(kvk.data, ["id"], {})
            return _numpy_dedup_select(lanes2, None)

        host["host_sort_ms"], take2 = best_of(h_sort)

        def h_decode_values():
            return [rf.read(f, fields=rest, system_columns=False) for f in files]

        host["decode_values_ms"], tails = best_of(h_decode_values)

        def h_gather():
            # the REAL pipeline gathers the full reassembled batch (keys +
            # concatenated value columns), not the keys-only head
            from paimon_tpu.data.batch import Column, ColumnBatch

            cols = {}
            for name in t.row_type.field_names:
                if name in key_cols:
                    cols[name] = kvk.data.column(name)
                else:
                    cols[name] = Column.concat([x.data.column(name) for x in tails])
            full = KVBatch(ColumnBatch(t.row_type, cols), kvk.seq, kvk.kind)
            return full.take(take2)

        host["gather_ms"], _ = best_of(h_gather)
        h_total = sum(host.values())
        for stage, v in host.items():
            print(
                json.dumps(
                    {"metric": f"merge-read.host.{stage[:-3]}",
                     "value": round(v * 1000, 2), "unit": "ms",
                     "share": round(v / h_total, 3)}
                ),
                flush=True,
            )
        print(
            json.dumps(
                {"metric": "merge-read.host.total", "value": round(h_total * 1000, 2),
                 "unit": "ms", "rows_per_s": round(args.rows / h_total, 1),
                 "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT}
            ),
            flush=True,
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Device-resident merge-kernel throughput.

The end-to-end bench (bench.py) includes host decode and the host<->device
transfers. This harness removes them from the measurement:
key/seq lanes are staged into device memory (HBM) first, then ONLY the
sort + segment + select kernel is timed (block_until_ready, best-of-N).
That number is the kernel-tier ceiling and the
answer to "how fast is the TPU merge itself vs the reference's heap loop"
(SortMergeReaderWithMinHeap.java:122-179, 975.4 Krows/s end-to-end parquet
scan baseline; the in-memory merge portion of the reference loop is what
this kernel replaces).

Grid: rows x lane-arity x engine(backend). Prints one JSON line per cell:
{"metric": "kernel.<engine>.k<K>s<S>", "value": rows/s, ...}.

Usage: python benchmarks/kernel_resident.py [--rows 1048576,4194304]
       [--engines dedup,dedup_pallas,partial_update]
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


def emit(metric, value, **extra):
    print(
        json.dumps(
            {"metric": metric, "value": round(value, 1), "unit": "rows/s",
             "vs_baseline": round(value / BASE, 3), "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT, **extra}
        ),
        flush=True,
    )


def make_lanes(n: int, k: int, s: int, dup_factor: int = 4, seed: int = 7):
    """Lanes shaped like a real merge: n rows over n/dup_factor distinct keys
    (4 overlapping runs), uint32, already in the kernel's (K, m) layout."""
    import jax

    from paimon_tpu.ops import merge as M

    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n // dup_factor, size=n, dtype=np.uint32)
    key_lanes = np.empty((n, k), dtype=np.uint32)
    key_lanes[:, 0] = keys
    for i in range(1, k):
        key_lanes[:, i] = keys * (i + 1) + 13  # correlated secondary lanes
    seq = np.arange(n, dtype=np.uint32)
    seq_lanes = np.empty((n, s), dtype=np.uint32)
    for i in range(s):
        seq_lanes[:, i] = seq
    klp, slp, pad, _, kk, ss, m = M.prepare_lanes(key_lanes, seq_lanes if s else None)
    dev = jax.devices()[0]
    return (
        jax.block_until_ready(jax.device_put(klp, dev)),
        jax.block_until_ready(jax.device_put(slp, dev)),
        jax.block_until_ready(jax.device_put(pad, dev)),
        kk,
        ss,
        m,
    )


def _chained(inner, chain_iters: int):
    """K data-dependent kernel invocations inside ONE jit: each iteration's
    keys are perturbed by the previous iteration's (data-dependent) count, so
    the device MUST run them sequentially and cannot reuse a cached result.
    One dispatch + one sync amortizes dispatch and fetch overhead over K
    real executions."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(key_lanes, seq_lanes, pad_flag, *extra):
        def body(_, carry):
            salt, acc = carry
            # cheap data dependency; keeps dtype + distribution (lanes may be
            # a list of mixed-dtype arrays after range narrowing)
            kl = [x ^ salt.astype(x.dtype) for x in key_lanes]
            out = inner(kl, seq_lanes, pad_flag, *extra)
            count = out[-1]  # every kernel returns (..., count)
            c = count.astype(jnp.uint32)
            return c % jnp.uint32(2), acc + c

        salt, acc = jax.lax.fori_loop(0, chain_iters, body, (jnp.uint32(0), jnp.uint32(0)))
        return acc

    return f


def _timed_value(fn, args, reps: int) -> float:
    """Best seconds-to-scalar-VALUE over reps (fetching the value
    synchronises with the device)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def time_kernel(inner, args, n_rows: int, k_lo: int = 4, k_hi: int = 32, reps: int = 3) -> float:
    """rows/s from the SLOPE between a short and a long kernel chain:
    t(K) ~= overhead + K * t_kernel, so t_kernel = (t(k_hi) - t(k_lo)) /
    (k_hi - k_lo). The intercept absorbs dispatch + fetch overhead."""
    f_lo, f_hi = _chained(inner, k_lo), _chained(inner, k_hi)
    float(f_lo(*args)), float(f_hi(*args))  # compile + warm both
    t_lo = _timed_value(f_lo, args, reps)
    t_hi = _timed_value(f_hi, args, reps)
    t_kernel = max((t_hi - t_lo) / (k_hi - k_lo), 1e-9)
    return n_rows / t_kernel


def bench_dedup(n: int, k: int, s: int, backend: str):
    from paimon_tpu.ops import merge as M

    klp, slp, pad, kk, ss, m = make_lanes(n, k, s)
    fn = M._dedup_select_fn(kk, ss, backend)
    rps = time_kernel(fn, (klp, slp, pad), n)
    tag = "dedup" if backend == "xla" else f"dedup_{backend}"
    emit(f"kernel.{tag}.k{kk}s{ss}", rps, rows=n, padded=m)


def bench_partial_update(n: int, k: int, s: int, fields: int = 4):
    import jax

    from paimon_tpu.ops import merge as M

    klp, slp, pad, kk, ss, m = make_lanes(n, k, s)
    rng = np.random.default_rng(11)
    dev = jax.devices()[0]
    fv = jax.block_until_ready(
        jax.device_put(rng.random((fields, m)) < 0.7, dev)
    )
    is_add = jax.block_until_ready(jax.device_put(np.ones(m, dtype=np.bool_), dev))
    is_del = jax.block_until_ready(jax.device_put(np.zeros(m, dtype=np.bool_), dev))
    fn = M._fused_partial_update_fn(kk, ss, fields)
    rps = time_kernel(fn, (klp, slp, pad, fv, is_add, is_del), n)
    emit(f"kernel.partial_update.k{kk}s{ss}f{fields}", rps, rows=n, padded=m)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1048576,4194304")
    ap.add_argument("--engines", default="dedup,dedup_pallas,partial_update")
    ap.add_argument("--arities", default="1:0,2:1")
    args = ap.parse_args()
    rows = [int(x) for x in args.rows.split(",")]
    engines = args.engines.split(",")
    arities = [tuple(int(v) for v in a.split(":")) for a in args.arities.split(",")]
    for n in rows:
        for k, s in arities:
            if "dedup" in engines:
                bench_dedup(n, k, s, "xla")
            if "dedup_pallas" in engines and PLATFORM != "cpu":
                bench_dedup(n, k, s, "pallas")
            if "partial_update" in engines:
                bench_partial_update(n, k, s)


if __name__ == "__main__":
    main()

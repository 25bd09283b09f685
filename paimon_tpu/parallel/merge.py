"""Distributed merge kernels: bucket data-parallelism + key-range parallelism.

Two levels, mirroring how the reference distributes work (SURVEY §2.9) but
expressed as XLA collectives instead of engine shuffle:

  bucket_parallel_dedup — buckets are key-disjoint, so B buckets' merges run
  as one shard_map over the "bucket" mesh axis with zero communication (the
  TPU analog of one Flink task per bucket).

  distributed_merge_step — one (huge) bucket's rows range-partitioned over
  the "key" mesh axis: sample splitters (all_gather), route rows to their
  range owner (all_to_all over ICI — Paimon's RangeShuffle analog,
  flink/shuffle/RangeShuffle.java), then sort-merge locally. Equal keys
  always land on one device (routing is by the most-significant key lane),
  so segments never straddle devices and the merge semantics stay exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.merge import _jit, _plan_fn

__all__ = [
    "bucket_parallel_dedup",
    "bucket_parallel_dedup_fn",
    "bucket_parallel_plan_fn",
    "range_partition_lanes",
    "range_partition_rows",
    "distributed_merge_step",
    "distributed_partial_update_step",
    "distributed_aggregate_step",
    "distributed_changelog_step",
]


def _local_plan(num_key: int, num_seq: int, key_lanes, seq_lanes, pad_flag):
    """(K,m),(S,m),(m,) -> perm, seg_start, keep_last, seg_id (single shard)."""
    return _plan_fn(num_key, num_seq)(key_lanes, seq_lanes, pad_flag)


# ---------------------------------------------------------------------------
# bucket axis: embarrassingly parallel per-bucket merges
# ---------------------------------------------------------------------------

def bucket_parallel_dedup(mesh: Mesh, key_lanes: np.ndarray, seq_lanes: np.ndarray, pad: np.ndarray):
    """key_lanes (B, m, K), seq_lanes (B, m, S), pad (B, m) uint32.
    Returns (perm, keep_last) each (B, m): per-bucket dedup selection, buckets
    sharded over the "bucket" axis. B must be divisible by the axis size."""
    b, m, k = key_lanes.shape
    s = seq_lanes.shape[2]

    def per_bucket(kl, sl, pf):
        # kl (m, K) -> (K, m)
        perm, _, keep_last, _ = _local_plan(k, s, kl.T, sl.T, pf)
        return perm, keep_last

    def shard_fn(kl, sl, pf):
        return jax.vmap(per_bucket)(kl, sl, pf)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("bucket", None, None), P("bucket", None, None), P("bucket", None)),
        out_specs=(P("bucket", None), P("bucket", None)),
    )
    return jax.jit(fn)(key_lanes, seq_lanes, pad)


@functools.lru_cache(maxsize=None)
def bucket_parallel_dedup_fn(mesh: Mesh, k: int, s: int):
    """Cached jit+shard_map of the DEDUP family over the mesh's bucket axis:
    (B, m, K) key lanes, (B, m, S) seq lanes, (B, m) pad -> per-bucket packed
    selected input indices + counts (the minimal download — pack_selected on
    device). The kernel body is ops.merge.sorted_segments/pack_selected, so
    mesh and single-device selection share one copy of the semantics. The
    cache key includes the Mesh (hashable, one per process via the executor's
    mesh factory), so each (mesh, lane arity) compiles exactly once."""
    from ..ops.merge import pack_selected, sorted_segments

    def per_bucket(kl, sl, pf):  # (m, K), (m, S), (m,)
        pad_sorted, perm, _, keep_last, _ = sorted_segments(k, s, kl.T, sl.T, pf)
        return pack_selected(keep_last & (pad_sorted == 0), perm)

    fn = shard_map(
        lambda kl, sl, pf: jax.vmap(per_bucket)(kl, sl, pf),
        mesh=mesh,
        in_specs=(P("bucket", None, None), P("bucket", None, None), P("bucket", None)),
        out_specs=(P("bucket", None), P("bucket")),
    )
    # a program name of its own (jit_dedup_select_mesh on the trace's XLA
    # Modules line), beginning as the single-device program's does
    return _jit("dedup_select_mesh")(fn)


@functools.lru_cache(maxsize=None)
def bucket_parallel_plan_fn(mesh: Mesh, k: int, s: int):
    """Cached jit+shard_map of the PLAN families (partial-update, aggregate,
    changelog rewrite — engines whose segment reductions finish host-side
    with arbitrary per-field aggregators) over the bucket axis: the full
    merge plan arrays (perm, seg_start, keep_last, seg_id) per bucket."""
    from ..ops.merge import sorted_segments

    def per_bucket(kl, sl, pf):
        _, perm, seg_start, keep_last, seg_id = sorted_segments(k, s, kl.T, sl.T, pf)
        return perm, seg_start, keep_last, seg_id

    fn = shard_map(
        lambda kl, sl, pf: jax.vmap(per_bucket)(kl, sl, pf),
        mesh=mesh,
        in_specs=(P("bucket", None, None), P("bucket", None, None), P("bucket", None)),
        out_specs=(
            P("bucket", None),
            P("bucket", None),
            P("bucket", None),
            P("bucket", None),
        ),
    )
    return _jit("merge_plan_mesh")(fn)


# ---------------------------------------------------------------------------
# key axis: range shuffle + local merge
# ---------------------------------------------------------------------------

def _range_exchange(
    key_lanes, seq_lanes, pad_flag, axis: str, p: int, num_key: int, num_seq: int,
    sample: int = 64, extra_lanes=None,
):
    """Runs INSIDE shard_map on the `axis` group. Inputs are this device's
    shard: key_lanes (K, m), seq_lanes (S, m), pad_flag (m,). Returns the
    re-partitioned shard (K, P*m), (S, P*m), (P*m,) where this device now
    owns a contiguous key range."""
    m = pad_flag.shape[0]
    lane0 = key_lanes[0]
    # --- splitters: evenly-spaced sample of each device's sorted lane0 ------
    big = jnp.uint32(0xFFFFFFFF)
    masked = jnp.where(pad_flag == 0, lane0, big)
    local_sorted = jnp.sort(masked)
    idx = jnp.linspace(0, m - 1, sample).astype(jnp.int32)
    local_sample = local_sorted[idx]
    all_samples = jax.lax.all_gather(local_sample, axis)  # (P, sample)
    flat = jnp.sort(all_samples.reshape(-1))
    cut = jnp.linspace(0, p * sample - 1, p + 1).astype(jnp.int32)[1:-1]
    splitters = flat[cut]  # (P-1,)
    # --- destination of each row -------------------------------------------
    dest = jnp.searchsorted(splitters, masked, side="right").astype(jnp.int32)
    dest = jnp.where(pad_flag == 0, dest, p - 1)  # pads route anywhere (stay padded)
    # --- group rows by destination into (P, m) send buffers -----------------
    iota = jnp.arange(m, dtype=jnp.int32)
    _, order = jax.lax.sort([dest, iota], num_keys=1, is_stable=True)
    dest_sorted = dest[order]
    ones = jnp.ones_like(dest_sorted)
    counts = jax.ops.segment_sum(ones, dest_sorted, num_segments=p)  # rows per dest
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    rank = iota - offsets[dest_sorted]  # position within its dest block
    # scatter into padded (P, m) buffers; unfilled slots stay pad
    def build(buf_dtype, values_sorted, fill):
        buf = jnp.full((p, m), fill, dtype=buf_dtype)
        return buf.at[dest_sorted, rank].set(values_sorted)

    send_pad = build(jnp.uint32, pad_flag[order], jnp.uint32(1))
    send_keys = [build(jnp.uint32, key_lanes[i][order], big) for i in range(num_key)]
    send_seqs = [build(jnp.uint32, seq_lanes[i][order], jnp.uint32(0)) for i in range(num_seq)]
    num_extra = 0 if extra_lanes is None else extra_lanes.shape[0]
    send_extra = [build(jnp.uint32, extra_lanes[i][order], jnp.uint32(0)) for i in range(num_extra)]
    # --- the collective ------------------------------------------------------
    def a2a(x):  # (P, m) -> (P, m): row i goes to device i
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)

    recv_pad = a2a(send_pad).reshape(-1)
    recv_keys = jnp.stack([a2a(x).reshape(-1) for x in send_keys], axis=0)
    recv_seqs = (
        jnp.stack([a2a(x).reshape(-1) for x in send_seqs], axis=0)
        if num_seq
        else jnp.zeros((0, p * m), jnp.uint32)
    )
    if extra_lanes is None:
        return recv_keys, recv_seqs, recv_pad
    recv_extra = (
        jnp.stack([a2a(x).reshape(-1) for x in send_extra], axis=0)
        if num_extra
        else jnp.zeros((0, p * m), jnp.uint32)
    )
    return recv_keys, recv_seqs, recv_pad, recv_extra


def range_partition_lanes(
    mesh: Mesh,
    key_lanes: np.ndarray,
    seq_lanes: np.ndarray,
    pad: np.ndarray,
    sample_per_device: int = 64,
):
    """Standalone range shuffle over the "key" axis (the distributed sort /
    clustering primitive). Inputs (n, K)/(n, S)/(n,) sharded on rows; output:
    per-device contiguous key ranges, each locally merged (perm + keep_last
    in the exchanged coordinate system). sample_per_device tunes splitter
    fidelity (reference sort-compaction.local-sample.magnification:
    sample = magnification x parallelism)."""
    n, k = key_lanes.shape
    s = seq_lanes.shape[1]
    p_key = mesh.shape["key"]

    def shard_fn(kl, sl, pf):
        rk, rs, rp = _range_exchange(
            kl.T, sl.T, pf, "key", p_key, k, s, sample=sample_per_device
        )
        perm, _, keep_last, _ = _local_plan(k, s, rk, rs, rp)
        # emit everything in SORTED order so row i of lanes aligns with
        # keep_last[i] / pad[i] (one coordinate system for downstream)
        return rk[:, perm].T, perm, keep_last, rp[perm]

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("key", None), P("key", None), P("key")),
        out_specs=(P("key", None), P("key"), P("key"), P("key")),
    )
    return jax.jit(fn)(key_lanes, seq_lanes, pad)


@functools.lru_cache(maxsize=None)
def _range_partition_rows_fn(mesh: Mesh, k: int, sample: int):
    """Cached kernel behind range_partition_rows: one row-id lane rides the
    all_to_all as the sole sequence lane, so after the exchange + local sort
    each device can name the GLOBAL input row at every sorted position."""
    p = mesh.shape["key"]

    def shard_fn(kl, rid, pf):
        rk, rs, rp = _range_exchange(kl.T, rid[None, :], pf, "key", p, k, 1, sample=sample)
        perm, _, _, _ = _local_plan(k, 1, rk, rs, rp)
        return rs[0][perm], rp[perm]

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("key", None), P("key"), P("key")),
        out_specs=(P("key"), P("key")),
    )
    return jax.jit(fn)


def range_partition_rows(
    mesh: Mesh,
    key_lanes: np.ndarray,
    row_ids: np.ndarray,
    pad: np.ndarray,
    sample_per_device: int = 64,
):
    """Globally-stable distributed sort of row ids by key: rows sharded over
    the "key" axis are range-shuffled to their owner (all_gather splitter
    sample + all_to_all — the RangeShuffle.java analog), locally sorted with
    the row id as the tie-break lane, and returned as (row_ids_sorted,
    pad_sorted) concatenated in ascending device-range order. Because routing
    is a pure function of the leading lane, device ranges are disjoint; and
    because the row id orders ties, the concatenation equals the SINGLE-device
    stable sort permutation bit-for-bit — the property sort-compact and
    dynamic-bucket rescale rely on (paimon_tpu.parallel.mesh_exec)."""
    n, k = key_lanes.shape
    out_rows, out_pad = _range_partition_rows_fn(mesh, k, sample_per_device)(
        key_lanes, row_ids, pad
    )
    return np.asarray(out_rows), np.asarray(out_pad)


# ---------------------------------------------------------------------------
# the full step: both axes composed (the dryrun_multichip target)
# ---------------------------------------------------------------------------

def distributed_merge_step(mesh: Mesh, key_lanes: np.ndarray, seq_lanes: np.ndarray, pad: np.ndarray):
    """One full distributed write/compact step on a (bucket, key) mesh:
    buckets sharded over "bucket" (pure data parallel), each bucket's rows
    sharded over "key" (range exchange + local merge). Shapes:
    key_lanes (B, n, K), seq_lanes (B, n, S), pad (B, n); B divisible by the
    bucket axis, n by the key axis. Returns (out_key_lanes, out_seq_lanes,
    perm, merged_valid) all in the post-exchange sorted coordinate system, so
    callers can check not just WHICH keys survived but which sequence number
    (i.e. which original row) won each key's merge."""
    b, n, k = key_lanes.shape
    s = seq_lanes.shape[2]
    p_key = mesh.shape["key"]

    def shard_fn(kl, sl, pf):
        # local shapes: kl (B_loc, n_loc, K), sl (B_loc, n_loc, S), pf (B_loc, n_loc)
        def one_bucket(kb, sb, pb):
            rk, rs, rp = _range_exchange(kb.T, sb.T, pb, "key", p_key, k, s)
            perm, _, keep_last, _ = _local_plan(k, s, rk, rs, rp)
            merged_valid = keep_last & (rp[perm] == 0)
            # sorted order: lanes[i] corresponds to merged_valid[i]
            return rk[:, perm].T, rs[:, perm].T, perm, merged_valid

        return jax.vmap(one_bucket)(kl, sl, pf)

    # each key-shard returns its received range block (rows grow to
    # p_key * n_loc locally => global row dim is p_key * n)
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("bucket", "key", None), P("bucket", "key", None), P("bucket", "key")),
        out_specs=(
            P("bucket", "key", None),
            P("bucket", "key", None),
            P("bucket", "key"),
            P("bucket", "key"),
        ),
    )
    return jax.jit(fn)(key_lanes, seq_lanes, pad)


def distributed_partial_update_step(
    mesh: Mesh,
    key_lanes: np.ndarray,  # (B, n, K) uint32
    seq_lanes: np.ndarray,  # (B, n, S) uint32
    pad: np.ndarray,  # (B, n) uint32
    field_valid: np.ndarray,  # (B, n, F) bool — per-field non-null mask
):
    """The partial-update merge engine ACROSS the range shuffle: per-field
    payload masks ride the all_to_all with the lanes; after the exchange each
    device owns a complete key range, so the per-key per-field "latest
    non-null wins" segment reduction (reference
    PartialUpdateMergeFunction.java:57) is locally exact.

    Returns (out_keys (B, N, K), out_seqs (B, N, S), merged_valid (B, N),
    field_src (B, F, N)) in the post-exchange SORTED coordinate system:
    field_src[b, f, i] is the sorted-row index holding field f's winning
    value for the key ending at sorted row i (-1 => field null), meaningful
    where merged_valid is True. out_seqs lets callers verify WHICH row won
    (latest-non-null contract), not just which key.
    """
    _, _, k = key_lanes.shape
    s = seq_lanes.shape[2]
    p_key = mesh.shape["key"]

    def shard_fn(kl, sl, pf, fv):
        def one_bucket(kb, sb, pb, fb):
            rk, rs, rp, rx = _range_exchange(
                kb.T, sb.T, pb, "key", p_key, k, s, extra_lanes=fb.T.astype(jnp.uint32)
            )
            perm, _, keep_last, seg_id = _local_plan(k, s, rk, rs, rp)
            m = rp.shape[0]
            from ..ops.merge import segment_last_where

            fv_sorted = rx[:, perm] != 0  # (F, m) in sorted coords
            last_per_field = segment_last_where(seg_id, fv_sorted)  # (F, m) by segment
            src = last_per_field[:, seg_id]  # broadcast back to rows
            merged_valid = keep_last & (rp[perm] == 0)
            # src is shard-local sorted position; offset to GLOBAL sorted
            # coords (each key-shard's block lands at axis_index * m)
            offset = jax.lax.axis_index("key").astype(jnp.int32) * m
            return (
                rk[:, perm].T,
                rs[:, perm].T,
                merged_valid,
                jnp.where(src >= 0, src + offset, -1),
            )

        return jax.vmap(one_bucket)(kl, sl, pf, fv)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P("bucket", "key", None),
            P("bucket", "key", None),
            P("bucket", "key"),
            P("bucket", "key", None),
        ),
        out_specs=(
            P("bucket", "key", None),
            P("bucket", "key", None),
            P("bucket", "key"),
            P("bucket", None, "key"),
        ),
    )
    return jax.jit(fn)(key_lanes, seq_lanes, pad, field_valid)

def _keyed_payload_step(mesh: Mesh, key_lanes, seq_lanes, pad, extra, payload_fn):
    """Shared scaffold for merge engines whose mesh form is: one uint32
    payload lane rides the all_to_all, then a per-segment reduction after the
    local plan. payload_fn(rx0, perm, seg_id, live, m) -> (m,) payload.
    Returns (out_keys (B, N, K), merged_valid (B, N), payload (B, N))."""
    _, _, k = key_lanes.shape
    s = seq_lanes.shape[2]
    p_key = mesh.shape["key"]

    def shard_fn(kl, sl, pf, xv):
        def one_bucket(kb, sb, pb, xb):
            rk, rs, rp, rx = _range_exchange(
                kb.T, sb.T, pb, "key", p_key, k, s, extra_lanes=xb[None, :]
            )
            perm, _, keep_last, seg_id = _local_plan(k, s, rk, rs, rp)
            live = rp[perm] == 0
            payload = payload_fn(rx[0], perm, seg_id, live, rp.shape[0])
            return rk[:, perm].T, keep_last & live, payload

        return jax.vmap(one_bucket)(kl, sl, pf, xv)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P("bucket", "key", None),
            P("bucket", "key", None),
            P("bucket", "key"),
            P("bucket", "key"),
        ),
        out_specs=(P("bucket", "key", None), P("bucket", "key"), P("bucket", "key")),
    )
    return jax.jit(fn)(key_lanes, seq_lanes, pad, extra)


def distributed_aggregate_step(
    mesh: Mesh,
    key_lanes: np.ndarray,  # (B, n, K) uint32
    seq_lanes: np.ndarray,  # (B, n, S) uint32
    pad: np.ndarray,  # (B, n) uint32
    values: np.ndarray,  # (B, n) float32 — the aggregated payload column
):
    """The AGGREGATION merge engine across the range shuffle (reference
    mergetree/compact/aggregate/FieldSumAgg.java under
    AggregateMergeFunction): payload values ride the all_to_all bitcast to
    uint32 lanes; after the exchange each device owns a complete key range,
    so the per-key segment SUM is locally exact. Insert-only rows (retract
    handling lives in the host aggregators, ops/aggregates.py).

    Returns (out_keys (B, N, K), merged_valid (B, N), sums (B, N)) in the
    post-exchange sorted coordinate system: sums[b, i] is key i's total where
    merged_valid[b, i] is True."""

    def seg_sum(rx0, perm, seg_id, live, m):
        vals = jax.lax.bitcast_convert_type(rx0, jnp.float32)[perm]
        vals = jnp.where(live, vals, 0.0)
        return jax.ops.segment_sum(vals, seg_id, num_segments=m)[seg_id]

    extra = jax.lax.bitcast_convert_type(jnp.asarray(values), jnp.uint32)
    return _keyed_payload_step(mesh, key_lanes, seq_lanes, pad, extra, seg_sum)


# changelog row codes emitted by distributed_changelog_step
CHANGELOG_NONE = 0     # key unchanged by this batch (or batch rows all lost)
CHANGELOG_INSERT = 1   # key is new: emit +I
CHANGELOG_UPDATE = 2   # key existed and the batch won: emit -U (old) / +U (new)


def distributed_changelog_step(
    mesh: Mesh,
    key_lanes: np.ndarray,  # (B, n, K) uint32 — OLD state rows + NEW batch rows
    seq_lanes: np.ndarray,  # (B, n, S) uint32 — new rows carry higher seqs
    pad: np.ndarray,  # (B, n) uint32
    is_new: np.ndarray,  # (B, n) uint32 — 1 = row belongs to the incoming batch
):
    """The changelog-producing rewrite ACROSS the mesh shuffle (reference
    mergetree/compact/ChangelogMergeTreeRewriter.java:47 /
    FullChangelogMergeFunctionWrapper): merge OLD top-level state with the
    NEW batch in one distributed pass and derive, per key, which changelog
    rows a full-compaction producer must emit — +I for a previously-unseen
    key, -U/+U when an existing key's winner comes from the batch, nothing
    when the batch lost or didn't touch the key. The is_new source flag rides
    the all_to_all with the lanes, so the derivation is exact after the
    exchange.

    Returns (out_keys (B, N, K), merged_valid (B, N), code (B, N)) sorted;
    code uses CHANGELOG_{NONE,INSERT,UPDATE}, meaningful where merged_valid
    (the code at a key's keep_last row decides from src_new there whether the
    winner came from the batch)."""

    def derive_code(rx0, perm, seg_id, live, m):
        src_new = (rx0[perm] != 0) & live
        src_old = (rx0[perm] == 0) & live
        any_new = jax.ops.segment_max(src_new.astype(jnp.int32), seg_id, num_segments=m)
        any_old = jax.ops.segment_max(src_old.astype(jnp.int32), seg_id, num_segments=m)
        return jnp.where(
            any_new[seg_id] == 0,
            CHANGELOG_NONE,
            jnp.where(
                any_old[seg_id] == 0,
                CHANGELOG_INSERT,
                jnp.where(src_new, CHANGELOG_UPDATE, CHANGELOG_NONE),
            ),
        )

    return _keyed_payload_step(
        mesh, key_lanes, seq_lanes, pad, jnp.asarray(is_new), derive_code
    )

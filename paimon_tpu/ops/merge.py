"""Sort + segment + select: the device merge kernel.

Replaces the reference's SortMergeReader heap loop and MergeFunction
application (/root/reference/paimon-core/.../mergetree/compact/
SortMergeReaderWithMinHeap.java:54-70 orders by (userKey, udsSeq, seqNumber);
:167-177 feeds same-key groups to the merge function). Here the ordering is
one stable lexicographic `lax.sort` and the per-key group logic is masks and
segment reductions — no data-dependent control flow, fully XLA-fusable.

Coordinate systems: "input" = row index into the concatenated runs;
"sorted" = position after the sort. `perm` maps sorted -> input.

Shapes: every device array is padded to a power-of-two bucket `m` so XLA
compiles once per (lane arity, size bucket). Pad rows carry a set pad flag
(the most significant sort lane), so valid rows occupy sorted slots [0, n)
and pad rows segment separately. The only dynamic-shape step — boolean
keep-mask -> index compaction — happens host-side in numpy where it's free.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import merge_metrics, span
from ..types import RowKind

__all__ = [
    "MergePlan",
    "merge_plan",
    "pad_size",
    "deduplicate_take",
    "first_row_take",
    "partial_update_takes",
]

_MIN_PAD = 128


def pad_size(n: int) -> int:
    """Next power of two (>=128): bounds the jit cache to O(log n) entries."""
    p = _MIN_PAD
    while p < n:
        p <<= 1
    return p


def pad_to(arr: np.ndarray, m: int, fill=0) -> np.ndarray:
    out = np.full((m,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _jit(name: str):
    """jax.jit under a stable program name: the trace's XLA Modules line and
    every device event read `jit_<name>`, so a program in a profile has an
    owner (each kernel here used to be `jit_f`)."""

    def named(f):
        f.__name__ = f.__qualname__ = name
        return jax.jit(f)

    return named


def _nbytes(operands) -> int:
    return sum(_nbytes(a) if isinstance(a, (list, tuple)) else a.nbytes for a in operands)


def _count_kernel(operands, rows: int, alloc_rows: int, tiles: int = 1) -> None:
    """merge{...} at one kernel call, from the shapes in hand: `operands`
    are the host arrays the jitted call is about to upload, `rows` the valid
    rows among the `alloc_rows` it sorts, in `tiles` sorts. The same numbers
    go onto the open span (merge.dispatch), which sums them over its calls."""
    h2d, pad = _nbytes(operands), alloc_rows - rows
    g = merge_metrics()
    g.counter("rows_in").inc(rows)
    g.counter("tiles").inc(tiles)
    g.counter("pad_rows").inc(pad)
    g.counter("h2d_bytes").inc(h2d)
    sp = span.current()
    if sp is not None:
        sp.add(tiles=tiles, pad_rows=pad, h2d_bytes=h2d)


def _count_download(nbytes: int, winners: int) -> None:
    """merge{...} at one resolve: bytes fetched from the device and rows the
    selection names; also onto the open span (merge.resolve)."""
    g = merge_metrics()
    g.counter("d2h_bytes").inc(nbytes)
    g.counter("winners").inc(winners)
    sp = span.current()
    if sp is not None:
        sp.add(d2h_bytes=nbytes, winners=winners)


# A device array of at most this many elements comes down whole and is cut on
# the host; a longer one is first cut on the device to one of _FETCH_STEPS
# lengths. Either way the XLA programs a download asks for do not follow the
# winner count: `x[:c]` on a device array is a new program for every c.
_FETCH_WHOLE_ELEMS = 1 << 20
_FETCH_STEPS = 8


def _fetch(arr, length: int, row: int | None = None) -> tuple[np.ndarray, int]:
    """(arr[..., :length] on the host, bytes that crossed the link); with
    `row`, of that row of a 2-D array alone, row and cut in one program."""
    m = arr.shape[-1]
    cut = m
    if (m if row is not None else arr.size) > _FETCH_WHOLE_ELEMS:
        step = max(1, m // _FETCH_STEPS)
        cut = min(m, -(-length // step) * step)
    if row is not None:
        arr = arr[row, :cut]
    elif cut < m:
        arr = arr[..., :cut]
    host = np.asarray(arr)
    return host[..., :length], host.nbytes


def sorted_segments(
    num_key_lanes: int, num_seq_lanes: int, key_lanes, seq_lanes, pad_flag, extra_keys=(), engine: str = "xla"
):
    """The shared in-kernel preamble (traced inside each jitted kernel): one
    stable lexicographic sort on (pad, key lanes, seq lanes, iota), then
    segment detection over (pad, key lanes) only — sequence lanes do NOT
    split segments (same key, different seq = one merge group). Returns
    (sorted_pad, perm, seg_start, keep_last, seg_id).

    Lane containers may be a (L, m) array OR a list of (m,) arrays of MIXED
    uint dtypes (the range-narrowed upload path) — per-lane indexing and
    per-lane compares avoid any cross-dtype stack.

    extra_keys: order-consistent leading key lanes (the offset-value code
    lane of ops/lanes.py) sorted between the pad flag and the key lanes and
    tested FIRST in boundary detection. An extra key must satisfy the OVC
    contract — where it differs it agrees with full-key order, where it ties
    the key lanes decide — so both the permutation and the segmentation stay
    bit-identical to the plain path.

    engine="pallas" is the sort-engine=pallas seam every merge kernel
    inherits: the sort stays `lax.sort` and the boundary mask comes from the
    pallas sweep kernel (ops/pallas_kernels.keep_last_mask), bit-identical
    to the plain path. A kernel the compiler refuses raises — the engine
    never quietly runs another."""
    m = pad_flag.shape[0]
    extra = list(extra_keys)
    boundary = [pad_flag] + extra + [key_lanes[i] for i in range(num_key_lanes)]
    order = [seq_lanes[i] for i in range(num_seq_lanes)]
    iota = jnp.arange(m, dtype=jnp.int32)
    operands = boundary + order + [iota]
    with jax.named_scope("merge.sort"):
        out = jax.lax.sort(operands, num_keys=len(operands) - 1, is_stable=True)
    perm = out[-1]
    if engine == "pallas":
        # lax.sort + the pallas boundary sweep (narrowed lanes may be
        # u8/u16 — widening on device costs nothing)
        from .pallas_kernels import keep_last_mask, pallas_interpret

        with jax.named_scope("merge.segment"):
            stacked = jnp.stack(
                [lane.astype(jnp.uint32) for lane in out[: len(boundary)]], axis=0
            )
            keep_last = keep_last_mask(stacked, interpret=pallas_interpret(), mask_pad=False).astype(
                jnp.bool_
            )
            seg_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), keep_last[:-1]])
            seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
        return out[0], perm, seg_start, keep_last, seg_id
    with jax.named_scope("merge.segment"):
        neq = jnp.zeros(m - 1, dtype=jnp.bool_)
        for lane in out[: len(boundary)]:
            neq = neq | (lane[1:] != lane[:-1])
        seg_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])
        keep_last = jnp.concatenate([neq, jnp.ones((1,), jnp.bool_)])
        seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    return out[0], perm, seg_start, keep_last, seg_id


def segment_last_where(seg_id, masks, pos=None):
    """In-kernel: per SEGMENT, the last sorted position where each mask row
    is True (-1 = none). masks (F, m) bool in SORTED coords; returns (F, m)
    indexed by segment id. The shared core of the planned partial-update
    selection and of the distributed range-shuffle engine's."""
    m = seg_id.shape[0]
    if pos is None:
        pos = jnp.arange(m, dtype=jnp.int32)
    cand = jnp.where(masks, pos[None, :], -1)
    return jax.vmap(lambda c: jax.ops.segment_max(c, seg_id, num_segments=m))(cand)


def pack_selected(sel, perm):
    """In-kernel: pack the selected perms to the front (key order) and count
    them — the minimal device->host transfer for selection kernels."""
    with jax.named_scope("merge.pack"):
        not_sel = (~sel).astype(jnp.uint32)
        _, packed = jax.lax.sort([not_sel, perm], num_keys=1, is_stable=True)
        return packed, sel.sum()


def _runid_bits(num_runs: int) -> int:
    """Bits per run-id in the compact selection encoding."""
    if num_runs <= 4:
        return 2
    if num_runs <= 16:
        return 4
    return 8


def _bitpack_rows(vals, rbits: int):
    """In-kernel: pack small uints (< 2^rbits) along the last axis,
    8/rbits per byte — the device half of _unpack_runids."""
    per = 8 // rbits
    r2 = vals.astype(jnp.uint8).reshape(vals.shape[:-1] + (vals.shape[-1] // per, per))
    byte = r2[..., 0]
    for i in range(1, per):
        byte = byte | (r2[..., i] << jnp.uint8(i * rbits))
    return byte


def _unpack_runids(packed: np.ndarray, c: int, rbits: int) -> np.ndarray:
    """Host: first c rbits-wide values from a _bitpack_rows byte stream
    (already on the host, at least ceil(c * rbits / 8) bytes of it)."""
    per = 8 // rbits
    pk = packed[: (c + per - 1) // per]
    if rbits == 8:
        return pk[:c]
    lanes = [(pk >> (i * rbits)) & ((1 << rbits) - 1) for i in range(per)]
    return np.stack(lanes, axis=1).ravel()[:c]


def _interleave_winners(winners: np.ndarray, rs: np.ndarray) -> np.ndarray:
    """Host: winners are grouped by run/block (ascending within each); rs is
    the run-id per output position. The stable argsort maps output positions
    ordered (run, output-order) onto winners element for element — radix
    argsort over small ints is O(c)."""
    out = np.empty(len(rs), dtype=np.int32)
    out[np.argsort(rs, kind="stable")] = winners
    return out


def pack_selection_compact(sel, perm, starts):
    """In-kernel epilogue: encode the selection as (a) a bit-packed keep-mask
    in INPUT coordinates and (b) bit-packed run-ids of the winners in key
    order. This shrinks the dominant device->host
    transfer ~10x vs int32 winner indices (m/8 bytes + c*rbits/8 bytes vs
    4c bytes); the host reconstructs the exact indices with O(c) numpy
    (unpack_selection_compact). Correctness rests on runs being key-sorted:
    within one run, winners ascend in both key and input index, so the
    keep-mask fixes each run's winner set and the run-id sequence fixes the
    interleave."""
    m = perm.shape[0]
    with jax.named_scope("merge.pack"):
        sel_input = jnp.zeros((m,), jnp.bool_).at[perm].set(sel)
        mask_bytes = jnp.packbits(sel_input)
        run_in = jnp.clip(
            jnp.searchsorted(starts, perm, side="right").astype(jnp.int32) - 1,
            0,
            starts.shape[0] - 1,
        )
        _, runs_key_order = jax.lax.sort(
            [(~sel).astype(jnp.uint32), run_in.astype(jnp.uint32)], num_keys=1, is_stable=True
        )
        byte = _bitpack_rows(runs_key_order, _runid_bits(starts.shape[0]))
        return mask_bytes, byte, sel.sum()


def unpack_selection_compact(mask_bytes, runs_packed, count, n: int, num_runs: int, rbits: int):
    """Host half of pack_selection_compact: (bit mask, packed run-ids, count)
    -> (selected input-row indices in global key order, bytes downloaded):
    the mask's first ceil(n/8) bytes and, with more than one run, the
    bit-packed run-ids of the winners, each through _fetch; nothing where
    nothing was selected. rbits comes from the dispatch handle (single
    source: _runid_bits over the padded starts the kernel actually saw)."""
    c = int(count)
    if c == 0:
        return np.empty(0, dtype=np.int32), 0
    mask, nbytes = _fetch(mask_bytes, (n + 7) // 8)
    keep = np.unpackbits(mask, count=n).astype(bool)
    winners = np.flatnonzero(keep).astype(np.int32)  # grouped by run, ascending
    if num_runs <= 1:
        return winners, nbytes
    per = 8 // rbits
    runs, runs_nbytes = _fetch(runs_packed, (c + per - 1) // per)
    return _interleave_winners(winners, _unpack_runids(runs, c, rbits)), nbytes + runs_nbytes


def narrow_lane(col: np.ndarray) -> np.ndarray:
    """Range-narrow one u32 lane for upload: subtract the min (a constant
    shift preserves order and segment boundaries) and downcast to u16 when
    the value range strictly fits (the dtype max is reserved as the pad
    sentinel). This halves the lane's upload bytes — the common case:
    dense ids, dictionary ranks, bucket-local sequence numbers.

    Deliberately TWO tiers only (u16/u32, no u8): each distinct dtype combo
    is a separate jit signature, so tiers trade link bytes against compile
    cache entries (2^(k+s) worst case; the persistent compile cache makes
    each a one-time cost). A batch whose range hovers around the u16
    boundary can flap tiers between merges — acceptable with the disk cache,
    revisit if profiles show recompile churn."""
    if col.size == 0:
        return col
    lo = col.min()
    ptp = int(col.max()) - int(lo)
    if ptp < np.iinfo(np.uint16).max:  # strict: sentinel must sort after
        return (col - lo).astype(np.uint16)
    return (col - lo).astype(np.uint32)


def prepare_lanes(key_lanes: np.ndarray, seq_lanes: np.ndarray | None, narrow: bool = True):
    """The shared host-side prep: drop constant lanes, range-narrow each
    remaining lane (u16 upload when the value range allows — half the
    host-to-device bytes), pad rows to the power-of-two
    bucket with max-sentinel keys + pad flags. Returns
    (klp, slp, pad, n, num_key, num_seq, m) where klp/slp are LISTS of (m,)
    arrays of possibly-mixed uint dtypes (not 2-D matrices — lanes narrow
    independently) and pad is (m,) u8."""
    key_lanes = np.ascontiguousarray(key_lanes)
    kl = drop_constant_lanes(key_lanes)
    sl = drop_constant_lanes(np.ascontiguousarray(seq_lanes)) if seq_lanes is not None else None
    n, k = kl.shape
    s = 0 if sl is None else sl.shape[1]
    m = pad_size(n)
    key_cols = [narrow_lane(kl[:, i]) if narrow else kl[:, i] for i in range(k)]
    klp = [np.full(m, np.iinfo(c.dtype).max, dtype=c.dtype) for c in key_cols]
    for buf, c in zip(klp, key_cols):
        buf[:n] = c
    seq_cols = [narrow_lane(sl[:, i]) if narrow else sl[:, i] for i in range(s)]
    slp = [np.zeros(m, dtype=c.dtype) for c in seq_cols]
    for buf, c in zip(slp, seq_cols):
        buf[:n] = c
    pad = np.zeros(m, dtype=np.uint8)
    pad[n:] = 1
    return klp, slp, pad, n, k, s, m


def _packed(key_lanes, compress: bool | None, plan):
    """(lanes', plan) behind the compression seam, for every dispatcher:
    lanes handed over WITH their plan were packed from the key columns
    (ops.lanes.compress_key_columns) and are not packed again; a raw (n, K)
    matrix is, or with the layer off loses its constant lanes (plan None)."""
    from .lanes import compress_key_lanes, resolve_compress

    if plan is not None:
        return key_lanes, plan
    key_lanes = np.ascontiguousarray(key_lanes)
    if resolve_compress(compress):
        return compress_key_lanes(key_lanes, True)
    return drop_constant_lanes(key_lanes), None


def prepare_lanes_planned(
    key_lanes: np.ndarray,
    seq_lanes: np.ndarray | None,
    narrow: bool = True,
    compress: bool | None = None,
    plan=None,
):
    """prepare_lanes behind the key-lane compression seam (ops/lanes.py):
    the key matrix is truncated/packed per a LanePlan before the usual
    narrowing + padding; lanes that come with their `plan` are packed
    already (ops.lanes.compress_key_columns) and pass the seam as they are.
    Returns (klp, slp, pad, n, k, s, m, plan); plan is
    None when the layer is off (k then counts post-drop_constant_lanes key
    lanes, exactly the legacy path). Either way an all-constant key yields
    k == 0 — callers take the zero-width scalar fast path instead of the old
    dummy-lane sort."""
    import dataclasses

    from .lanes import compress_key_lanes

    kl = key_lanes
    if plan is None:
        kl, plan = compress_key_lanes(np.ascontiguousarray(key_lanes), compress)
    klp, slp, pad, n, k, s, m = prepare_lanes(kl, seq_lanes, narrow=narrow)
    if plan is not None and plan.use_ovc and kl.shape[0]:
        # narrow_lane min-shifts every uploaded column; the OVC base must
        # shift identically so the in-kernel lane==base compares match the
        # packed-space comparison exactly (a shared constant shift per column
        # preserves ==, <, and the code's value-field bound)
        if narrow:
            mins = kl.min(axis=0)
            plan = dataclasses.replace(
                plan, base=tuple(int(b) - int(mn) for b, mn in zip(plan.base, mins))
            )
    return klp, slp, pad, n, k, s, m, plan


@functools.lru_cache(maxsize=None)
def _plan_fn(num_key_lanes: int, num_seq_lanes: int, ovc_vbits: int = 0, engine: str = "xla"):
    """Builds the jitted sort+segment kernel for a lane arity. ovc_vbits > 0
    adds the device-computed offset-value code as the leading key (and the
    base values as a traced (G,) operand). engine routes the preamble
    through the sort-engine seam (pallas = lax.sort + pallas boundary sweep)."""
    if ovc_vbits:
        from .lanes import ovc_codes_jax

        @_jit("merge_plan_ovc")
        def f_ovc(key_lanes, seq_lanes, pad_flag, base):
            code = ovc_codes_jax(
                [key_lanes[i] for i in range(num_key_lanes)], base, ovc_vbits
            )
            _, perm, seg_start, keep_last, seg_id = sorted_segments(
                num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag,
                extra_keys=(code,), engine=engine,
            )
            return perm, seg_start, keep_last, seg_id

        return f_ovc

    @_jit("merge_plan")
    def f(key_lanes, seq_lanes, pad_flag):
        # key/seq lanes: (K, m)/(S, m) arrays OR lists of (m,) mixed-dtype
        # uint arrays (narrowed upload); pad_flag: (m,) uint
        _, perm, seg_start, keep_last, seg_id = sorted_segments(
            num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag, engine=engine
        )
        return perm, seg_start, keep_last, seg_id

    return f


@dataclass
class MergePlan:
    """Sorted view of the concatenated inputs of one merge. Arrays have
    padded length m; valid rows occupy sorted slots [0, n)."""

    perm: np.ndarray  # (m,) sorted -> input row index (int32)
    seg_start: np.ndarray  # (m,) bool, sorted coords
    keep_last: np.ndarray  # (m,) bool, sorted coords (last row of segment)
    seg_id: np.ndarray  # (m,) int32, sorted coords
    n: int  # valid rows
    m: int  # padded size

    @property
    def valid_sorted(self) -> np.ndarray:
        return np.arange(self.m) < self.n

    @property
    def num_segments(self) -> int:
        """Segments holding valid rows (pad segments sort after them)."""
        return int(self.seg_id[self.n - 1]) + 1 if self.n else 0


def drop_constant_lanes(lanes: np.ndarray) -> np.ndarray:
    """A lane equal everywhere affects neither ordering nor segmentation —
    dropping it shrinks host->device transfer and sort width (the common case:
    int64 keys/seqnos whose high 32 bits are constant within one merge)."""
    n, k = lanes.shape
    if n <= 1 or k == 0:
        return lanes
    keep = [i for i in range(k) if lanes[0, i] != lanes[-1, i] or (lanes[:, i] != lanes[0, i]).any()]
    if len(keep) == k:
        return lanes
    return lanes[:, keep] if keep else lanes[:, :0]


def merge_plan(
    key_lanes: np.ndarray,
    seq_lanes: np.ndarray | None = None,
    compress: bool | None = None,
    engine: str = "xla",
) -> MergePlan:
    """key_lanes: (n, K) uint32. seq_lanes: (n, S) uint32 ordering within a
    key group (user-defined sequence lanes first, then sequence-number lanes —
    the reference's (udsSeq, seqNumber) tie-break). Stable: remaining ties
    resolve to input order, which is run order — same as the heap's reader
    index tie-break.

    Callers whose input rows are already seq-ascending within equal keys
    (runs with disjoint seq ranges concatenated in seq order) may pass
    seq_lanes=None: stability makes explicit sequence lanes redundant.

    compress routes the key matrix through the lane-compression layer
    (ops/lanes.py: truncation + packing + OVC) — bit-identical plan, fewer
    sort operands; None resolves to the merge.lane-compression default."""
    from .lanes import compress_key_lanes, resolve_compress

    key_lanes = np.ascontiguousarray(key_lanes)
    seq_keep = drop_constant_lanes(np.ascontiguousarray(seq_lanes)) if seq_lanes is not None else None
    if resolve_compress(compress):
        kl_kept, plan = compress_key_lanes(key_lanes, True)
    else:
        kl_kept, plan = drop_constant_lanes(key_lanes), None
    if kl_kept.shape[1] == 0 and (seq_keep is None or seq_keep.shape[1] == 0):
        # all keys equal (or no rows) and nothing to order by: the zero-width
        # scalar fast path — one segment of valid rows in input order, no
        # sort dispatched at all (the old path kept a dummy constant lane
        # "for shape sanity" and sorted it anyway)
        return _scalar_plan(key_lanes.shape[0])
    return _merge_plan_padded(kl_kept, seq_keep, plan, engine)


def _scalar_plan(n: int) -> MergePlan:
    """Host-built MergePlan for the zero-width key, zero seq-lane case: the
    stable sort of (pad, iota) is the identity, valid rows form one segment
    and pads another — exactly what the k=0 kernel would return, without the
    device trip."""
    m = pad_size(n)
    perm = np.arange(m, dtype=np.int32)
    seg_start = np.zeros(m, dtype=np.bool_)
    seg_start[0] = True
    keep_last = np.zeros(m, dtype=np.bool_)
    keep_last[m - 1] = True
    if 0 < n < m:
        seg_start[n] = True
        keep_last[n - 1] = True
    seg_id = (np.cumsum(seg_start) - 1).astype(np.int32)
    return MergePlan(perm=perm, seg_start=seg_start, keep_last=keep_last, seg_id=seg_id, n=n, m=m)


def _merge_plan_padded(
    key_lanes: np.ndarray, seq_lanes: np.ndarray | None, plan=None, engine: str = "xla"
) -> MergePlan:
    n, k = key_lanes.shape
    if seq_lanes is None:
        seq_lanes = np.zeros((n, 0), dtype=np.uint32)
    s = seq_lanes.shape[1]
    m = pad_size(n)
    kl = np.full((k, m), 0xFFFFFFFF, dtype=np.uint32)
    kl[:, :n] = key_lanes.T
    sl = np.zeros((s, m), dtype=np.uint32)
    sl[:, :n] = seq_lanes.T
    pad = np.zeros(m, dtype=np.uint32)
    pad[n:] = 1
    use_ovc = plan is not None and plan.use_ovc
    timer = None
    if engine == "pallas":
        from ..metrics import pallas_metrics, timed
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
        # this path resolves synchronously just below (np.asarray), so the
        # wall time around dispatch+download is the kernel latency
        timer = timed(pallas_metrics().histogram("kernel_ms"))
        timer.__enter__()
    merge_metrics().counter("merges").inc()
    with span("merge.dispatch", rows=n):
        if use_ovc:
            # this path uploads unshifted u32 lanes, so the packed-space base
            # passes through unshifted too
            operands = (kl, sl, pad, np.asarray(plan.base, dtype=np.uint32))
            fn = _plan_fn(k, s, plan.ovc_vbits, engine)
        else:
            operands, fn = (kl, sl, pad), _plan_fn(k, s, 0, engine)
        _count_kernel(operands, n, m)
        outs = fn(*operands)
    with span("merge.resolve"):
        perm, seg_start, keep_last, seg_id = (np.asarray(o) for o in outs)
        _count_download(_nbytes((perm, seg_start, keep_last, seg_id)), 0)
    if timer is not None:
        timer.__exit__(None, None, None)
    return MergePlan(perm=perm, seg_start=seg_start, keep_last=keep_last, seg_id=seg_id, n=n, m=m)


def deduplicate_take(plan: MergePlan) -> np.ndarray:
    """Input-row indices of each key's last (key, seq) row — the deduplicate
    merge engine (reference DeduplicateMergeFunction.java:31: last row wins).
    Output is in key order."""
    return plan.perm[plan.keep_last & plan.valid_sorted]


@functools.lru_cache(maxsize=None)
def _dedup_select_fn(num_key_lanes: int, num_seq_lanes: int, backend: str = "xla", ovc_vbits: int = 0):
    """Sort + keep-last + device-side compaction: returns ONLY the selected
    input indices (packed to the front) and their count — the minimal
    device->host transfer for the dominant dedup path. backend="pallas"
    computes the boundary mask with the pallas sweep kernel through the
    sorted_segments seam;
    ovc_vbits > 0 computes the offset-value code lane on device and leads
    the sort + boundary detection with it (ops/lanes.py) — composing with
    either engine."""
    if ovc_vbits:
        from .lanes import ovc_codes_jax

        @_jit("dedup_select_ovc")
        def f_ovc(key_lanes, seq_lanes, pad_flag, base):
            code = ovc_codes_jax(
                [key_lanes[i] for i in range(num_key_lanes)], base, ovc_vbits
            )
            pad_sorted, perm, _, keep_last, _ = sorted_segments(
                num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag,
                extra_keys=(code,), engine=backend,
            )
            return pack_selected(keep_last & (pad_sorted == 0), perm)

        return f_ovc

    @_jit("dedup_select")
    def f(key_lanes, seq_lanes, pad_flag):
        pad_sorted, perm, _, keep_last, _ = sorted_segments(
            num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag, engine=backend
        )
        sel = keep_last & (pad_sorted == 0)  # exclude pad rows
        return pack_selected(sel, perm)

    return f


def deduplicate_select_async(
    key_lanes: np.ndarray,
    seq_lanes: np.ndarray | None = None,
    backend: str = "xla",
    compress: bool | None = None,
    plan=None,
):
    """Dispatch the dedup kernel without blocking: returns (packed_device,
    count_device). jax's async dispatch lets the host keep decoding value
    columns while the device sorts — resolve with deduplicate_resolve().
    The key matrix goes through the lane-compression seam first (key lanes
    that come with their `plan` have passed it: _packed); an
    all-constant key short-circuits to the scalar winner without any device
    dispatch."""
    with span("merge.dispatch", rows=len(key_lanes)):
        if len(key_lanes) > _STREAM_TILE_ROWS:
            handle = _stream_dispatch(key_lanes, seq_lanes, backend, compress, plan)
            if handle is not None:
                return handle
        return _select_async(key_lanes, seq_lanes, backend, compress, merges=1, plan=plan)


# A merge of more rows than this runs as key-range tiles of this one padded
# shape, a call a tile: the writers' merges (a flush, a compaction round) grow
# with the table, and a sort program per power of two that a cascade reaches
# costs seconds to a minute each, met at any time in a table's life. One
# shape is one program, whatever the merge's size; it is also the pad bucket
# of a memtable of 65,537 to 131,072 rows, so such a writer's flushes and its
# compaction rounds share it.
_STREAM_TILE_ROWS = 1 << 17


def _stream_dispatch(key_lanes, seq_lanes, backend: str, compress: bool | None, plan=None):
    """Key-range tiles of an input in ANY row order, all of the padded shape
    (_STREAM_TILE_ROWS,) and of u32 lanes, so every tile of every merge with
    the same lane arity is the same program. Tiles cut the key space on the
    most significant packed lane, so every duplicate of a key lands in one
    tile; rows keep their input order inside a tile, so stability carries
    the tie-break as it does for the whole. Returns ("stream", [(handle,
    input rows of the tile)]) in ascending key-range order, or None where
    the keys cannot be cut that finely (one lane-0 value holds more rows
    than a tile): the caller then sorts the whole at its own pad bucket."""
    n = key_lanes.shape[0]
    lanes, plan = _packed(key_lanes, compress, plan)
    if lanes.shape[1] == 0:
        return None  # all keys equal: the scalar path, no device trip
    seqs = drop_constant_lanes(np.ascontiguousarray(seq_lanes)) if seq_lanes is not None else None
    lane0 = lanes[:, 0]
    num_tiles = -(-n * 5 // (_STREAM_TILE_ROWS * 4))  # aim at tiles four fifths full
    sample = np.sort(lane0[:: max(1, n // 65536)])
    for _ in range(3):
        cuts = np.unique(sample[np.linspace(0, len(sample) - 1, num_tiles + 1).astype(np.int64)[1:-1]])
        tile_of = np.searchsorted(cuts, lane0, side="right").astype(np.uint16)
        sizes = np.bincount(tile_of, minlength=len(cuts) + 1)
        if sizes.max() <= _STREAM_TILE_ROWS:
            break
        num_tiles *= 2
    else:
        return None
    order = np.argsort(tile_of, kind="stable").astype(np.int32)  # radix: O(n)
    use_ovc = plan is not None and plan.use_ovc
    k, s, m = lanes.shape[1], 0 if seqs is None else seqs.shape[1], _STREAM_TILE_ROWS
    fn = _dedup_select_fn(k, s, backend, plan.ovc_vbits if use_ovc else 0)
    base = (np.asarray(plan.base, dtype=np.uint32),) if use_ovc else ()
    merge_metrics().counter("merges").inc()
    handles, at = [], 0
    for size in sizes.tolist():
        if not size:
            continue
        rows = order[at : at + size]
        at += size
        klp = [pad_to(lanes[rows, i], m, 0xFFFFFFFF) for i in range(k)]
        slp = [pad_to(seqs[rows, i], m) for i in range(s)]
        pad = np.zeros(m, dtype=np.uint8)
        pad[size:] = 1
        if backend == "pallas":
            from .pallas_kernels import note_dispatch

            note_dispatch(m)
        operands = (klp, slp, pad) + base
        _count_kernel(operands, size, m)
        handles.append((fn(*operands), rows))  # async: the next tile assembles while this sorts
    return ("stream", handles)


def _select_async(key_lanes, seq_lanes, backend: str, compress: bool | None, merges: int = 0, plan=None):
    """deduplicate_select_async without its span; `merges` is 1 where the
    call is a whole merge and 0 where it is one tile of a merge that its
    caller counts."""
    klp, slp, pad, n, k, s, m, plan = prepare_lanes_planned(key_lanes, seq_lanes, compress=compress, plan=plan)
    if k == 0:
        # all keys equal: one winner — the last row in (seq, input) order;
        # no key sort, no device trip (host lexsort of the seq lanes only)
        from .lanes import scalar_dedup_winner

        return ("scalar", scalar_dedup_winner(seq_lanes, n))
    use_ovc = plan is not None and plan.use_ovc
    if backend == "pallas":
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
    if use_ovc:
        operands = (klp, slp, pad, np.asarray(plan.base, dtype=np.uint32))
        fn = _dedup_select_fn(k, s, backend, plan.ovc_vbits)
    else:
        operands, fn = (klp, slp, pad), _dedup_select_fn(k, s, backend)
    merge_metrics().counter("merges").inc(merges)
    _count_kernel(operands, n, m)
    return fn(*operands)


def _link_encodings_pay_off() -> bool:
    """Compact/delta selection encodings trade device+host pack/unpack work
    for host<->device bytes. On the CPU backend there IS no link — "device"
    arrays are host memory — so the encodings are pure overhead.
    PAIMON_TPU_FORCE_COMPACT=1 overrides so tests exercise the device
    dispatch policy on CPU."""
    if os.environ.get("PAIMON_TPU_FORCE_COMPACT", "") == "1":
        return True
    return not resolved_platform_is_cpu()


def resolved_platform_is_cpu() -> bool:
    """True when the live JAX backend is the CPU (covers JAX's own
    fall-through to cpu when no accelerator initialises)."""
    return jax.default_backend() == "cpu"


def _real_starts(run_offsets: Sequence[int]) -> list[int]:
    """Start offsets of the NON-EMPTY runs (a filtered-out file yields a
    duplicate offset) — the single source for run filtering shared by the
    wide-compact and delta-packed paths."""
    starts = [s for s, e in zip(run_offsets[:-1], run_offsets[1:]) if e > s]
    return starts or [0]


def _pad_starts(starts_real: Sequence[int], m: int) -> np.ndarray:
    """Pad run starts to a pow2 length (min 4) so jit signatures stay
    bounded; pad entries point past the end (m) and thus never win a
    searchsorted. The padded length also fixes the run-id bit width
    (_runid_bits) on both device and host."""
    rp = 4
    while rp < len(starts_real):
        rp <<= 1
    out = np.full(rp, m, dtype=np.int32)
    out[: len(starts_real)] = starts_real
    return out


@functools.lru_cache(maxsize=None)
def _dedup_select_compact_fn(num_key_lanes: int, num_seq_lanes: int, ovc_vbits: int = 0, engine: str = "xla"):
    """Sort + keep-last + compact-encoded selection: the downlink-minimal
    dedup kernel (bit-packed keep-mask + run-id interleave instead of int32
    indices). ovc_vbits > 0 leads sort + boundary detection with the
    device-computed offset-value code lane; engine routes the preamble
    through the sort-engine seam."""
    if ovc_vbits:
        from .lanes import ovc_codes_jax

        @_jit("dedup_select_compact_ovc")
        def f_ovc(key_lanes, seq_lanes, pad_flag, starts, base):
            code = ovc_codes_jax(
                [key_lanes[i] for i in range(num_key_lanes)], base, ovc_vbits
            )
            pad_sorted, perm, _, keep_last, _ = sorted_segments(
                num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag,
                extra_keys=(code,), engine=engine,
            )
            return pack_selection_compact(keep_last & (pad_sorted == 0), perm, starts)

        return f_ovc

    @_jit("dedup_select_compact")
    def f(key_lanes, seq_lanes, pad_flag, starts):
        pad_sorted, perm, _, keep_last, _ = sorted_segments(
            num_key_lanes, num_seq_lanes, key_lanes, seq_lanes, pad_flag, engine=engine
        )
        sel = keep_last & (pad_sorted == 0)
        return pack_selection_compact(sel, perm, starts)

    return f


def deduplicate_select_compact_async(
    key_lanes: np.ndarray, run_offsets: Sequence[int], compress: bool | None = None, backend: str = "xla"
):
    """Compact-download dispatch for run-structured inputs (each run
    key-sorted ascending). Returns an opaque handle for
    deduplicate_resolve(), or None above 256 runs (run-ids are u8 on
    device; the caller falls back to the index-download path). Requires no
    explicit seq lanes (run order + sort stability carries the sequence
    tie-break)."""
    starts_real = _real_starts(run_offsets)
    if len(starts_real) > 256:
        return None  # run-ids are u8 on device
    klp, slp, pad, n, k, s, m, plan = prepare_lanes_planned(key_lanes, None, compress=compress)
    if k == 0:
        from .lanes import scalar_dedup_winner

        return ("scalar", scalar_dedup_winner(None, n))
    starts_p = _pad_starts(starts_real, m)
    use_ovc = plan is not None and plan.use_ovc
    if backend == "pallas":
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
    if use_ovc:
        operands = (klp, slp, pad, starts_p, np.asarray(plan.base, dtype=np.uint32))
        fn = _dedup_select_compact_fn(k, s, plan.ovc_vbits, backend)
    else:
        operands, fn = (klp, slp, pad, starts_p), _dedup_select_compact_fn(k, s, 0, backend)
    _count_kernel(operands, n, m)
    return ("compact", fn(*operands), n, len(starts_real), _runid_bits(len(starts_p)))


def pack_delta_runs(col: np.ndarray, run_offsets: Sequence[int]):
    """Delta-pack one u32 lane of ascending key-sorted runs for upload:
    u16 within-run deltas + per-run u32 bases; the device reconstructs the
    lane exactly with one cumsum. Halves the dominant link bytes for dense
    keys (the VERDICT r2 #2 'delta/bit-packed lane upload'). Returns
    (deltas u16 (m,), starts i32 (R,), bases u32 (R,), pad u8 (m,), n, m,
    num_real_runs) or None when any within-run delta exceeds u16 (caller
    falls back wide)."""
    n = len(col)
    if n == 0:
        return None
    if int(col.max()) - int(col.min()) < 0xFFFF:
        # the whole range fits u16: narrow_lane's wide path already uploads
        # the same bytes — delta packing would be pure overhead
        return None
    starts = np.asarray(_real_starts(run_offsets), dtype=np.int64)
    d = np.zeros(n, dtype=np.int64)
    d[1:] = col[1:].astype(np.int64) - col[:-1].astype(np.int64)
    d[starts] = 0  # run boundaries carry the base instead
    if d.min() < 0 or d.max() > 0xFFFF:
        return None  # not ascending / sparse keys: wide path wins
    m = pad_size(n)
    deltas = np.zeros(m, dtype=np.uint16)
    deltas[:n] = d.astype(np.uint16)
    r = len(starts)
    starts_p = _pad_starts(starts.tolist(), m)
    bases_p = np.zeros(len(starts_p), dtype=np.uint32)
    bases_p[:r] = col[starts]
    pad = np.zeros(m, dtype=np.uint8)
    pad[n:] = 1
    return deltas, starts_p, bases_p, pad, n, m, r


def _delta_reconstruct_lane(deltas, starts, bases, pad_flag):
    """In-kernel: rebuild the u32 key lane from the delta-packed upload
    (one cumsum + per-run rebase) — shared by both delta epilogues."""
    m = pad_flag.shape[0]
    with jax.named_scope("merge.reconstruct"):
        iota = jnp.arange(m, dtype=jnp.int32)
        c = jnp.cumsum(deltas.astype(jnp.uint32), dtype=jnp.uint32)
        run = jnp.clip(
            jnp.searchsorted(starts, iota, side="right").astype(jnp.int32) - 1,
            0,
            starts.shape[0] - 1,
        )
        lane = bases[run] + (c - c[starts[run]])
        return jnp.where(pad_flag == 0, lane, jnp.uint32(0xFFFFFFFF))


@functools.lru_cache(maxsize=None)
def _dedup_select_delta_fn(backend: str = "xla"):
    """The dedup kernel for delta-packed single-lane keys: reconstruct the
    u32 lane on device (cumsum + per-run rebase), then the standard
    sort + keep-last epilogue with the compact-encoded download."""

    @_jit("dedup_select_delta")
    def f(deltas, starts, bases, pad_flag):
        lane = _delta_reconstruct_lane(deltas, starts, bases, pad_flag)
        pad_sorted, perm, _, keep_last, _ = sorted_segments(1, 0, [lane], [], pad_flag, engine=backend)
        sel = keep_last & (pad_sorted == 0)
        return pack_selection_compact(sel, perm, starts)

    return f


@functools.lru_cache(maxsize=None)
def _dedup_select_delta_wide_fn(backend: str = "xla"):
    """Delta-packed UPLOAD with the legacy index DOWNLOAD (pack_selected):
    keeps the halved uplink bytes when the compact download encoding is
    unavailable — run counts past its u8 run-id limit (>256)."""

    @_jit("dedup_select_delta_wide")
    def f(deltas, starts, bases, pad_flag):
        lane = _delta_reconstruct_lane(deltas, starts, bases, pad_flag)
        pad_sorted, perm, _, keep_last, _ = sorted_segments(1, 0, [lane], [], pad_flag, engine=backend)
        sel = keep_last & (pad_sorted == 0)
        return pack_selected(sel, perm)

    return f


def deduplicate_select_delta_async(key_lanes: np.ndarray, run_offsets: Sequence[int], backend: str = "xla"):
    """Delta-packed dispatch for single-lane run-sorted keys; None when the
    lane does not qualify (multi-lane, non-ascending, sparse deltas, or a
    range the u16 narrowing already covers). Above 256 runs the upload
    stays delta-packed but the download falls back to packed indices
    (_dedup_select_delta_wide_fn). Both downloads route the sort+boundary
    preamble through the sort-engine seam."""
    if key_lanes.shape[1] != 1:
        return None
    packed = pack_delta_runs(key_lanes[:, 0], run_offsets)
    if packed is None:
        return None
    deltas, starts, bases, pad, n, m, num_runs = packed
    if backend == "pallas":
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
    _count_kernel((deltas, starts, bases, pad), n, m)
    if num_runs > 256:
        return _dedup_select_delta_wide_fn(backend)(deltas, starts, bases, pad)
    outs = _dedup_select_delta_fn(backend)(deltas, starts, bases, pad)
    return ("compact", outs, n, num_runs, _runid_bits(len(starts)))


def _dedup_dispatch(key_lanes: np.ndarray, run_offsets: Sequence[int], backend: str):
    """One dispatch-policy site: delta-packed upload when it qualifies,
    compact (bit-packed) download when the run count allows, wide
    index-download otherwise. On the CPU backend every encoding is skipped
    (_link_encodings_pay_off): there are no link bytes to save. Callers
    (the tiled dispatcher) have already run the lane-compression seam, so
    every path here suppresses it (compress=False) — plans are made once
    per merge, not once per tile. The sort-engine seam (backend) composes
    with every encoding: the link format is independent of which kernel
    computes the sort + boundary."""
    if not _link_encodings_pay_off():
        return _select_async(key_lanes, None, backend, False)
    handle = deduplicate_select_delta_async(key_lanes, run_offsets, backend=backend)
    if handle is not None:
        return handle
    handle = deduplicate_select_compact_async(key_lanes, run_offsets, compress=False, backend=backend)
    if handle is None:  # >256 runs: index-download fallback
        handle = _select_async(key_lanes, None, backend, False)
    return handle


def deduplicate_resolve(handle) -> np.ndarray:
    """Block on the device, then fetch the selection a dispatch produced."""
    with span("merge.resolve"):
        return _resolve(handle)


def _resolve(handle) -> np.ndarray:
    if isinstance(handle, tuple) and handle[0] == "scalar":
        return handle[1]  # zero-width fast path: host-computed winner(s)
    if isinstance(handle, tuple) and handle[0] == "compact":
        _, (mask_bytes, runs_packed, count), n, num_runs, rbits = handle
        c = int(count)
        take, nbytes = unpack_selection_compact(mask_bytes, runs_packed, c, n, num_runs, rbits)
        _count_download(count.nbytes + nbytes, c)
        return take
    if isinstance(handle, tuple) and handle[0] == "stream":
        out = [rows[_resolve(tile)] for tile, rows in handle[1]]
        return np.concatenate(out) if out else np.empty(0, dtype=np.int32)
    packed, count = handle
    c = int(count)
    take, nbytes = _fetch(packed, c)
    _count_download(count.nbytes + nbytes, c)
    return take


def deduplicate_select(
    key_lanes: np.ndarray, seq_lanes: np.ndarray | None = None, compress: bool | None = None
) -> np.ndarray:
    """Fused dedup: input lanes -> selected input-row indices (key order).
    Equivalent to deduplicate_take(merge_plan(...)) with ~3x less transfer."""
    return deduplicate_resolve(deduplicate_select_async(key_lanes, seq_lanes, compress=compress))


def deduplicate_select_tiled(
    key_lanes: np.ndarray,
    run_offsets: Sequence[int],
    tile_rows: int = 256 * 1024,
    backend: str = "xla",
    compress: bool | None = None,
) -> np.ndarray:
    """Key-range tiled dedup for runs concatenated in ascending-seq order
    (stability replaces seq lanes; see merge_plan docstring).

    The input is a concatenation of key-sorted runs (run r occupies rows
    [run_offsets[r], run_offsets[r+1])). Tiles cut the key space on the most
    significant lane — every duplicate of a key lands in exactly one tile —
    and each tile's kernel is dispatched asynchronously, so host<->device
    transfers of tile t+1 overlap the device sort of tile t. This is also the
    blockwise path for sections larger than device memory (the reference
    spills via MergeSorter :110-116; we tile by key range instead).
    Returns selected input-row indices in global key order."""
    return deduplicate_resolve_tiled(
        deduplicate_tiled_dispatch(key_lanes, run_offsets, tile_rows, backend, compress=compress)
    )


@functools.lru_cache(maxsize=None)
def _dedup_select_batched_fn(num_key_lanes: int):
    """vmapped sort + keep-last + pack over a (T, m) tile batch: every tile
    of a key-range tiled merge runs in ONE dispatch under ONE compile
    signature. This replaced the per-tile dispatch whose varying pad buckets
    and narrowing dtypes caused a fresh compile per tile."""

    @_jit("dedup_select_batched")
    def f(key_lanes, pad_flag):
        def per_tile(kl, pf):  # kl: tuple of (m,) uint lanes; pf: (m,) u8
            pad_sorted, perm, _, keep_last, _ = sorted_segments(
                num_key_lanes, 0, kl, [], pf
            )
            return pack_selected(keep_last & (pad_sorted == 0), perm)

        return jax.vmap(per_tile)(key_lanes, pad_flag)

    return f


# one batched tile dispatch stays under this many uint32-equivalent words
_TILE_BATCH_BUDGET_WORDS = 64 * 1024 * 1024


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p <<= 1
    return p


def _tile_boundaries(lane0_runs: list[np.ndarray], num_tiles: int) -> np.ndarray:
    """Approximate global quantiles of lane0 from per-run subsamples (each
    run is key-sorted): balanced tiles regardless of how rows distribute
    across runs. Unique boundaries keep every duplicate key in one tile."""
    total = sum(len(r) for r in lane0_runs)
    step = max(1, total // 65536)
    sample = np.sort(np.concatenate([r[::step] for r in lane0_runs]))
    cut_idx = np.linspace(0, len(sample) - 1, num_tiles + 1).astype(np.int64)[1:-1]
    return np.unique(sample[cut_idx])


def _gather_tiles(key_lanes, offsets, lane0_runs, boundaries):
    """Cut every run at the key boundaries and concatenate run slices per
    tile (run order preserved — stability carries the sequence tie-break).
    Returns [(tile_lanes (nt, k) u32, tile_global_rows (nt,) i32), ...] for
    the non-empty tiles, in ascending key-range order."""
    per_run_cuts = [np.searchsorted(lr, boundaries, side="left") for lr in lane0_runs]
    tiles = []
    for t in range(len(boundaries) + 1):
        slices, rows = [], []
        for r, lr in enumerate(lane0_runs):
            lo = 0 if t == 0 else int(per_run_cuts[r][t - 1])
            hi = len(lr) if t == len(boundaries) else int(per_run_cuts[r][t])
            if hi > lo:
                base = offsets[r]
                slices.append(key_lanes[base + lo : base + hi])
                rows.append(np.arange(base + lo, base + hi, dtype=np.int32))
        if slices:
            tiles.append(
                (
                    np.concatenate(slices) if len(slices) > 1 else slices[0],
                    np.concatenate(rows) if len(rows) > 1 else rows[0],
                )
            )
    return tiles


def deduplicate_tiled_dispatch(
    key_lanes: np.ndarray,
    run_offsets: Sequence[int],
    tile_rows: int = 256 * 1024,
    backend: str = "xla",
    compress: bool | None = None,
    plan=None,
):
    """Async dispatch of the key-range tiled dedup; resolve with
    deduplicate_resolve_tiled. Key lanes that come with their `plan` are
    packed already (_packed).

    Uniform-batch design (VERDICT r4 #2): all tiles share one pad bucket
    m = pad_size(max tile rows), one narrowing dtype per lane (u16 iff every
    tile's range fits), and one chunk shape (T_chunk, m) — so the whole
    multi-tile merge compiles exactly ONE kernel, which the persistent
    compile cache then serves to every later merge at this tile size.
    Chunks are dispatched back-to-back without blocking; sections larger
    than the device budget stream through as equal-shaped chunks (the
    reference spills to disk instead: MergeSorter.java:110-116)."""
    with span("merge.dispatch", rows=len(key_lanes)):
        return _tiled_dispatch(key_lanes, run_offsets, tile_rows, backend, compress, plan)


def _tiled_dispatch(key_lanes, run_offsets, tile_rows: int, backend: str, compress: bool | None, plan=None):
    n = key_lanes.shape[0]
    offsets = list(run_offsets)
    if n == 0:
        return []
    from .lanes import scalar_dedup_winner

    # one compression plan for the whole merge; tiles inherit the packed
    # lanes (row order is untouched, so run offsets and the per-run key
    # ascent the tiler depends on both survive the transform)
    key_lanes, _plan = _packed(key_lanes, compress, plan)
    if key_lanes.shape[1] == 0:
        # all keys equal: one winner (no seq lanes on this path — run order
        # + stability carries the tie-break, so the winner is the last row)
        return [(("scalar", scalar_dedup_winner(None, n)), np.arange(n, dtype=np.int32))]
    merge_metrics().counter("merges").inc()
    if n <= tile_rows or len(offsets) < 3:
        return [(_dedup_dispatch(key_lanes, offsets, backend), np.arange(n, dtype=np.int32))]
    lane0_runs = [key_lanes[offsets[r] : offsets[r + 1], 0] for r in range(len(offsets) - 1)]
    num_tiles = max(2, (n + tile_rows - 1) // tile_rows)
    boundaries = _tile_boundaries(lane0_runs, num_tiles)
    tiles = _gather_tiles(key_lanes, offsets, lane0_runs, boundaries)
    if len(tiles) == 1 or backend == "pallas":
        # pallas epilogue is benchmarked per-tile; a single tile needs no batch
        handles = []
        for tile_lanes, tile_global in tiles:
            handles.append((_dedup_dispatch(tile_lanes, [0, len(tile_lanes)], backend), tile_global))
        return handles

    k = key_lanes.shape[1]
    m = pad_size(max(t[0].shape[0] for t in tiles))
    # uniform per-lane narrowing: u16 only when EVERY tile's range fits (one
    # dtype signature for the whole batch; per-tile min-shift keeps the win)
    mins = np.stack([t[0].min(axis=0) for t in tiles])  # (T, k)
    ptp_max = (np.stack([t[0].max(axis=0) for t in tiles]) - mins).max(axis=0)
    dtypes = [np.uint16 if int(p) < 0xFFFF else np.uint32 for p in ptp_max]

    words_per_tile = m * (len(dtypes) + 1)  # conservative: u16 lanes count full
    t_chunk = _pow2_at_least(len(tiles))
    max_chunk = max(1, _TILE_BATCH_BUDGET_WORDS // max(words_per_tile, 1))
    while t_chunk > max_chunk and t_chunk > 1:
        t_chunk >>= 1

    fn = _dedup_select_batched_fn(k)
    chunks = []
    for c0 in range(0, len(tiles), t_chunk):
        chunk = tiles[c0 : c0 + t_chunk]
        lanes_b = tuple(
            np.full((t_chunk, m), np.iinfo(d).max, dtype=d) for d in dtypes
        )
        pad_b = np.ones((t_chunk, m), dtype=np.uint8)
        for i, (tl, _) in enumerate(chunk):
            nt = tl.shape[0]
            for j in range(k):
                lanes_b[j][i, :nt] = (tl[:, j] - mins[c0 + i, j]).astype(dtypes[j])
            pad_b[i, :nt] = 0
        _count_kernel((lanes_b, pad_b), sum(len(rows) for _, rows in chunk), t_chunk * m, tiles=len(chunk))
        outs = fn(lanes_b, pad_b)  # async: next chunk assembles while this sorts
        chunks.append((outs, [rows for _, rows in chunk]))
    return ("batched", chunks)


def deduplicate_resolve_tiled(handles) -> np.ndarray:
    with span("merge.resolve"):
        return _resolve_tiled(handles)


def _resolve_tiled(handles) -> np.ndarray:
    if isinstance(handles, tuple) and handles[0] == "batched":
        out = []
        for (packed, counts), rows_list in handles[1]:
            counts_np = np.asarray(counts)
            live = counts_np[: len(rows_list)]
            # a small chunk comes down in one piece; a long one a tile at a
            # time, as separate host arrays: one array of both 8M-row tiles
            # is past glibc's largest mmap threshold (32 MB) and would be
            # mapped and faulted anew in every operation
            whole = packed.size <= _FETCH_WHOLE_ELEMS
            packed_np, nbytes = _fetch(packed, int(live.max())) if whole else (None, 0)
            for t, rows in enumerate(rows_list):
                c = int(counts_np[t])
                if c:
                    if whole:
                        take = packed_np[t, :c]
                    else:
                        take, tile_nbytes = _fetch(packed, c, row=t)
                        nbytes += tile_nbytes
                    out.append(rows[take])
            _count_download(counts_np.nbytes + nbytes, int(live.sum()))
        return np.concatenate(out) if out else np.empty(0, dtype=np.int32)
    out = []
    for handle, rows in handles:
        local = _resolve(handle)
        out.append(rows[local])
    return np.concatenate(out) if out else np.empty(0, dtype=np.int32)


def first_row_take(plan: MergePlan) -> np.ndarray:
    """First row per key (reference FirstRowMergeFunction.java)."""
    return plan.perm[plan.seg_start & plan.valid_sorted]


@functools.lru_cache(maxsize=None)
def _partial_update_fn():
    @_jit("partial_update")
    def f(perm, seg_id, field_valid, is_add, is_delete):
        # perm/seg_id: (m,) sorted coords; field_valid (F, m), is_add (m,),
        # is_delete (m,) in INPUT coords, padded with False
        m = perm.shape[0]
        pos = jnp.arange(m, dtype=jnp.int32)
        add_sorted = is_add[perm]
        del_sorted = is_delete[perm]
        # last delete position per segment (-1 if none)
        del_cand = jnp.where(del_sorted, pos, -1)
        last_del = jax.ops.segment_max(del_cand, seg_id, num_segments=m)
        gate = pos[None, :] > last_del[seg_id][None, :]
        fv_sorted = field_valid[:, perm]  # (F, m)
        last_per_field = segment_last_where(seg_id, fv_sorted & add_sorted[None, :] & gate, pos)
        src = jnp.where(last_per_field >= 0, perm[jnp.clip(last_per_field, 0, m - 1)], -1)
        # segment produces a row iff any add row after its last delete
        add_cand = jnp.where(add_sorted, pos, -1)
        last_add = jax.ops.segment_max(add_cand, seg_id, num_segments=m)
        exists = last_add > last_del
        return src, exists

    return f


@functools.lru_cache(maxsize=None)
def _fused_partial_update_fn(num_key: int, num_seq: int, num_fields: int, engine: str = "xla"):
    """Sort + segment + partial-update selection in ONE kernel: the plan never
    leaves the device. It returns sorted positions, each key's in key order:
    per field the position of the key's winning row (-1 where the field
    stays null), whether the key exists, the key's last row, and the sort's
    permutation, which maps positions to input rows on the host
    (fused_partial_update).

    A per-key quantity is a running maximum of sorted positions read at the
    key's last row (`ends`, packed to the front): the key's last row where a
    mask holds, unless it reaches back before the key's first row. The chip
    pays per index of a gather or a scatter, whatever it moves, so the masks
    travel as bits of one word a row through the sort's permutation, the
    running maxima are read at `ends` as rows of F + 3, and the winners
    come down as positions, not as input rows (a gather a field and key);
    no scatter, so no index sort."""
    num_words = -(-(num_fields + 2) // 32)

    @_jit("fused_partial_update")
    def f(key_lanes, seq_lanes, pad_flag, field_valid, is_add, is_delete):
        pad_sorted, perm, seg_start, keep_last, _ = sorted_segments(
            num_key, num_seq, key_lanes, seq_lanes, pad_flag, engine=engine
        )
        pos = jnp.arange(perm.shape[0], dtype=jnp.int32)
        ends, count = pack_selected(keep_last & (pad_sorted == 0), pos)
        # bit b of the words: field b's validity for b < F, then the add flag, then the delete flag
        flags = [field_valid[i] for i in range(num_fields)] + [is_add, is_delete]
        words = jnp.stack([
            functools.reduce(jnp.bitwise_or, [
                flags[b].astype(jnp.uint32) << (b % 32) for b in range(32 * w, min(32 * w + 32, len(flags)))])
            for w in range(num_words)])[:, perm]

        def bit(b):
            return ((words[b // 32] >> (b % 32)) & 1).astype(jnp.bool_)

        add, delete = bit(num_fields), bit(num_fields + 1)
        masks = [bit(i) & add for i in range(num_fields)] + [add, delete]
        last = jax.lax.cummax(jnp.stack([jnp.where(mk, pos, -1) for mk in masks] + [jnp.where(seg_start, pos, -1)]),
                              axis=1)[:, ends]
        last = jnp.where(last[:-1] >= last[-1], last[:-1], -1)  # the last row reads the key's first row
        last_field, last_add, last_del = last[:num_fields], last[num_fields], last[num_fields + 1]
        # a field's value is its last non-null add; an add before the key's last delete is void
        src = jnp.where(last_field > last_del, last_field, -1)
        return src, last_add > last_del, ends, perm, count

    return f


def fused_partial_update(
    key_lanes: np.ndarray,  # (n, K) uint32
    seq_lanes: np.ndarray | None,  # (n, S) uint32
    field_valid: np.ndarray,  # (F, n) bool
    row_kind: np.ndarray,  # (n,) uint8
    remove_record_on_delete: bool = False,
    compress: bool | None = None,
    engine: str = "xla",
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Single-call partial-update merge: returns (src (F, k), exists (k,),
    last_take (k,)) in key order — the same contract as
    merge_plan + partial_update_takes + keep-last takes, one device trip.
    The selection comes down as it is, int32 sorted positions and the
    sort's permutation, which maps them to input rows on the host: an
    encoding that ships fewer bytes, or the map made on the chip, costs the
    chip more time than the link or the host saves.
    Key lanes run through the compression seam (truncate + pack); an
    all-constant key sorts on sequence lanes alone (k=0 kernel). The host
    work up to the call, lanes packed and every operand padded, is
    `merge.dispatch` (the validity masks' padding inside it `pu.valid`); the
    downloads `merge.resolve`; the map of positions to input rows
    `pu.unpack`."""
    from ..types import RowKind

    F = field_valid.shape[0]
    with span("merge.dispatch", rows=len(key_lanes)):
        klp, slp, pad, n, k, s, m, _plan = prepare_lanes_planned(key_lanes, seq_lanes, compress=compress)
        is_add = np.isin(row_kind, (int(RowKind.INSERT), int(RowKind.UPDATE_AFTER)))
        if remove_record_on_delete:
            is_delete = row_kind == int(RowKind.DELETE)
        else:
            is_delete = np.zeros_like(is_add)
        with span("pu.valid", rows=n, fields=F):
            fv = np.zeros((F, m), dtype=np.bool_)
            fv[:, :n] = field_valid
        if engine == "pallas":
            from .pallas_kernels import note_dispatch

            note_dispatch(m)
        merge_metrics().counter("merges").inc()
        operands = (klp, slp, pad, fv, pad_to(is_add, m, False), pad_to(is_delete, m, False))
        _count_kernel(operands, n, m)
        src, exists, ends, perm, count = _fused_partial_update_fn(k, s, F, engine)(*operands)
    with span("merge.resolve"):
        kk = int(count)
        (src_np, src_nbytes), (exists_np, exists_nbytes), (ends_np, ends_nbytes), (perm_np, perm_nbytes) = (
            _fetch(src, kk), _fetch(exists, kk), _fetch(ends, kk), _fetch(perm, n))
        _count_download(count.nbytes + src_nbytes + exists_nbytes + ends_nbytes + perm_nbytes, kk)
    with span("pu.unpack", rows_out=kk, fields=F):
        rows = np.empty(n + 1, dtype=np.int32)  # input row at each sorted position; -1, a null, reads the last
        rows[:n] = perm_np
        rows[n] = -1
        return rows[src_np], exists_np, rows[ends_np]


def partial_update_takes(
    plan: MergePlan,
    field_valid: np.ndarray,  # (F, n) bool — per merged field, non-null mask (input coords)
    row_kind: np.ndarray,  # (n,) uint8 (input coords)
    remove_record_on_delete: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Partial-update merge engine (reference PartialUpdateMergeFunction.java:57):
    per field, the output value is the field's latest non-null value in
    (key, seq) order. Returns (src, exists) sliced to the valid segments:
    src (F, num_segments) input-row index per field (-1 => null), exists
    (num_segments,) bool — False when remove-record-on-delete dropped the row.
    """
    m = plan.m
    is_add = np.isin(row_kind, (int(RowKind.INSERT), int(RowKind.UPDATE_AFTER)))
    if remove_record_on_delete:
        is_delete = row_kind == int(RowKind.DELETE)
    else:
        is_delete = np.zeros_like(is_add)
    src, exists = _partial_update_fn()(
        jnp.asarray(plan.perm),
        jnp.asarray(plan.seg_id),
        jnp.asarray(pad_to(field_valid.T, m, False).T if field_valid.shape[1] != m else field_valid),
        jnp.asarray(pad_to(is_add, m, False)),
        jnp.asarray(pad_to(is_delete, m, False)),
    )
    k = plan.num_segments
    return np.asarray(src)[:, :k], np.asarray(exists)[:k]

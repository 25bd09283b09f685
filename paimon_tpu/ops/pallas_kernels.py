"""Pallas TPU kernel for the merge hot path.

Selected by table option `sort-engine=pallas` (CoreOptions.SortEngine): the
merge keeps the stable lexicographic `lax.sort` and computes the
run-boundary / keep-last mask with the **boundary-sweep kernel**
(`keep_last_mask`): a bandwidth-bound elementwise pass detecting segment
boundaries across all key lanes at once, each grid step loading a block of
the stacked lanes plus a one-element lookahead. `interpret=True` runs the
same kernel on CPU so CI proves bit-identical output without hardware; on a
TPU it is compiled by Mosaic, and a kernel the compiler refuses raises.

The engines agree: numpy oracle (sort-engine=numpy) == xla-segmented ==
pallas, asserted per-seed by tests/test_pallas_merge.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "keep_last_mask",
    "note_dispatch",
    "pallas_interpret",
]

_BLOCK = 2048
# block indices are int32 for Mosaic: under the package's x64 mode a bare
# Python 0 in an index map traces as int64, which it refuses to legalize
_I0 = np.int32(0)


def pallas_interpret() -> bool:
    """interpret=True whenever the live backend is CPU: the same kernel
    trace serves CI (interpreted) and the chip (Mosaic-compiled)."""
    return jax.default_backend() == "cpu"


def note_dispatch(m: int) -> None:
    """Host-side metric hook for a sort-engine=pallas dispatch of m padded
    rows: pallas{kernels_launched, tiles} (one grid step per _BLOCK rows)."""
    from ..metrics import pallas_metrics

    padded, block = _sweep_block(m)
    g = pallas_metrics()
    g.counter("kernels_launched").inc()
    g.counter("tiles").inc(padded // block)


def _keep_last_kernel_factory(mask_pad: bool):
    def _keep_last_kernel(cur_ref, nxt_ref, out_ref):
        cur = cur_ref[...]  # (L, B) — stacked pad+key lanes
        nxt = nxt_ref[...]  # (L, B) — the following block (clamped at the end)
        # "next element" of each position: shift left, last column from the
        # lookahead block's first column
        shifted = jnp.concatenate([cur[:, 1:], nxt[:, :1]], axis=1)
        # stay 2D throughout (mosaic wants tiled vectors) and avoid reductions
        # (unsigned reductions are unimplemented): fold lanes with bitwise-or,
        # the lane count is static and small
        xor = cur ^ shifted
        diff = xor[0:1, :]
        for i in range(1, xor.shape[0]):
            diff = diff | xor[i : i + 1, :]
        neq = jnp.where(diff != 0, jnp.uint32(1), jnp.uint32(0))
        if mask_pad:
            not_pad = jnp.where(cur[0:1, :] == 0, jnp.uint32(1), jnp.uint32(0))
            neq = neq * not_pad
        out_ref[...] = neq  # (1, B) uint32

    return _keep_last_kernel


def _sweep_block(m: int) -> tuple[int, int]:
    """(padded size, block) for the boundary sweep: the grid must tile m
    exactly, so non-multiples are padded up — to the next multiple of 128
    under one block, of _BLOCK beyond (the old wrapper silently REQUIRED
    m % 128 == 0 and truncated the tail otherwise)."""
    if m <= _BLOCK:
        m2 = ((m + 127) // 128) * 128
        return m2, m2
    m2 = ((m + _BLOCK - 1) // _BLOCK) * _BLOCK
    return m2, _BLOCK


@functools.partial(jax.jit, static_argnames=("interpret", "mask_pad"))
def keep_last_mask(stacked: jax.Array, interpret: bool = False, mask_pad: bool = True) -> jax.Array:
    """stacked: (L, m) uint32, lane 0 = pad flag, lanes 1.. = key lanes,
    rows sorted. Returns (m,) uint32: 1 where the row is the last of its
    segment (mask_pad=True additionally zeroes pad rows — the legacy dedup
    contract; mask_pad=False returns the raw sorted_segments keep_last,
    where the trailing pad segment closes too). Any m >= 1 is accepted:
    non-multiples of the block are padded inside the wrapper with pad-flag
    rows whose boundary against the true last row closes its segment."""
    l, m = stacked.shape
    m2, block = _sweep_block(m)
    if m2 != m:
        ext = jnp.zeros((l, m2 - m), dtype=stacked.dtype)
        # synthetic pad rows: pad flag set, key lanes zero — they differ
        # from any real last row in lane 0, closing its segment exactly
        ext = ext.at[0, :].set(jnp.uint32(1))
        stacked = jnp.concatenate([stacked, ext], axis=1)
    grid = m2 // block
    last_block = grid - 1

    out = pl.pallas_call(
        _keep_last_kernel_factory(mask_pad),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((l, block), lambda i: (_I0, i)),
            # lookahead: the next block (the final block reads itself; the
            # wrapper forces the true last element below)
            pl.BlockSpec((l, block), lambda i: (_I0, jnp.minimum(i + 1, jnp.int32(last_block)))),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (_I0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m2), jnp.uint32),
        interpret=interpret,
    )(stacked, stacked)
    out = out[0, :m]
    # the global last element has no successor: it always closes its segment
    # (under mask_pad, only when it is not padding)
    if mask_pad:
        last_valid = jnp.where(stacked[0, m - 1] == 0, jnp.uint32(1), jnp.uint32(0))
    else:
        last_valid = jnp.uint32(1)
    return out.at[m - 1].set(last_valid)

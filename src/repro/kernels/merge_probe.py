"""Pallas TPU kernel: sorted-key merge probe for the sort-merge join.

Given two ascending int32 key arrays (the packed join keys of both sides
of an equi-join, invalid rows carrying distinct top-of-range sentinels),
produce for every a-key the half-open range of equal b-keys:

    start[i] = #{j : b[j] <  a[i]}     (== searchsorted left)
    cnt[i]   = #{j : b[j] == a[i]}     (== right - left)

The expand/gather step of the join consumes (start, cnt) directly.

TPU mapping: a is reshaped to (rows, 128) lanes and tiled over grid dim 0;
b is walked in (8, 128) blocks (one int32 vreg tile, 1024 keys) over grid
dim 1, accumulating lt/eq counts into the revisited output block (the
standard accumulation pattern).  Because both sides are sorted, each b
block first compares its min/max against the a tile's range: blocks
entirely below contribute a uniform +TILE_B to `start`, blocks entirely
above contribute nothing.  A boundary-overlapping block repeats the same
test per 128-key row, and only rows that overlap the a tile run the
lane-unrolled compare loop — the merge property that makes this
near-linear despite the tiled formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_R = 8          # sublane rows per a tile -> 8*128 keys
B_ROWS = 8                  # lane rows per b block: one (8, 128) int32 tile
TILE_B = B_ROWS * 128       # b keys per block

_I32_MAX = jnp.iinfo(jnp.int32).max


def _kernel(a_ref, b_ref, start_ref, cnt_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        start_ref[...] = jnp.zeros_like(start_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    a = a_ref[...]                              # [TR, 128] sorted overall
    a_min = jnp.min(a)
    a_max = jnp.max(a)
    b = b_ref[...]                              # [B_ROWS, 128] sorted
    below = b[B_ROWS - 1, 127] < a_min          # whole block < every a key
    above = b[0, 0] > a_max                     # whole block > every a key

    @pl.when(below)
    def _all_below():
        start_ref[...] += jnp.full(start_ref.shape, TILE_B, jnp.int32)

    @pl.when(jnp.logical_not(below | above))
    def _overlap():
        def row(r, carry):
            br = b_ref[pl.ds(r, 1), :]          # [1, 128] sorted
            r_below = br[0, 127] < a_min
            r_above = br[0, 0] > a_max

            @pl.when(r_below)
            def _row_below():
                start_ref[...] += jnp.full(start_ref.shape, 128, jnp.int32)

            @pl.when(jnp.logical_not(r_below | r_above))
            def _row_overlap():
                lt = jnp.zeros(a.shape, jnp.int32)
                eq = jnp.zeros(a.shape, jnp.int32)
                for j in range(128):
                    bj = br[0, j]
                    lt += (bj < a).astype(jnp.int32)
                    eq += (bj == a).astype(jnp.int32)
                start_ref[...] += lt
                cnt_ref[...] += eq
            return carry

        jax.lax.fori_loop(0, B_ROWS, row, 0)


@functools.partial(jax.jit, static_argnames=("tile_r", "interpret"))
def merge_probe_pallas(a_keys: jax.Array, b_keys: jax.Array,
                       *, tile_r: int = DEFAULT_TILE_R,
                       interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """a_keys [A] int32 ascending, b_keys [B] int32 ascending.

    Real keys must be < INT32_MAX - 1 (the join packs keys into
    [0, 2^31 - 3] and reserves the top two values for invalid-row
    sentinels); kernel padding uses INT32_MAX which sorts last and never
    equals a real key.  Returns (start [A], cnt [A]) int32.
    """
    a = jnp.asarray(a_keys, jnp.int32)
    b = jnp.asarray(b_keys, jnp.int32)
    n_a, n_b = a.shape[0], b.shape[0]

    span = tile_r * 128
    a_pad = -(-max(n_a, 1) // span) * span
    b_pad = -(-max(n_b, 1) // TILE_B) * TILE_B
    a_p = jnp.full((a_pad,), _I32_MAX, jnp.int32).at[:n_a].set(a)
    b_p = jnp.full((b_pad,), _I32_MAX, jnp.int32).at[:n_b].set(b)
    a_m = a_p.reshape(a_pad // 128, 128)
    b_m = b_p.reshape(b_pad // 128, 128)

    grid = (a_pad // span, b_pad // TILE_B)
    start, cnt = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, 128), lambda i, k: (i, 0)),
            pl.BlockSpec((B_ROWS, 128), lambda i, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_r, 128), lambda i, k: (i, 0)),
            pl.BlockSpec((tile_r, 128), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((a_pad // 128, 128), jnp.int32),
            jax.ShapeDtypeStruct((a_pad // 128, 128), jnp.int32),
        ],
        interpret=interpret,
    )(a_m, b_m)
    start = start.reshape(-1)[:n_a]
    cnt = cnt.reshape(-1)[:n_a]
    # kernel padding of b (INT32_MAX) is > every real key, so it never
    # perturbs `start`; it only inflates `cnt` for a-keys that are
    # themselves INT32_MAX (the caller's invalid-row sentinel) — subtract
    # that contribution so invalid rows report zero matches.
    pad_b = b_pad - n_b
    if pad_b:
        cnt = jnp.where(a == _I32_MAX, cnt - pad_b, cnt)
    return start, cnt

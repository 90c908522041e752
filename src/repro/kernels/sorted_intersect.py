"""Pallas TPU kernel: batched id-list intersection test.

The core of the paper's Algorithm 3 (Connectivity Check): for P candidate
pairs, test whether the forward neighbor-id list of n_i intersects the
backward neighbor-id list of n_j.  Lists are -1 padded.

TPU mapping: pairs are laid along lanes, 128 per grid step; both lists
arrive transposed ([A, P] and [B, P]), so list position is the sublane
axis.  A loop walks the B list one sublane row at a time and compares it
against the whole A block (an O(A*B) VPU compare-reduce).  The loop is
not unrolled, so the working set stays at the two blocks
(128 * (A + B) ints) whatever the list widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_P = 128                # pairs per tile, one lane row


def _kernel(a_ref, b_ref, out_ref):
    a = a_ref[...]                                  # [A, TP]

    def col(j, hit):
        bj = b_ref[pl.ds(j, 1), :]                  # [1, TP]
        m = jnp.where((a == bj) & (bj >= 0), 1, 0)
        return jnp.maximum(hit, jnp.max(m, axis=0, keepdims=True))

    hit = jax.lax.fori_loop(0, b_ref.shape[0], col,
                            jnp.zeros((1, a.shape[1]), jnp.int32))
    out_ref[...] = jnp.broadcast_to(hit, out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_any_pallas(a: jax.Array, b: jax.Array,
                         *, interpret: bool = False) -> jax.Array:
    """a [P, A] int32, b [P, B] int32 (-1 padded) -> hit [P] int32."""
    p, a_w = a.shape
    _, b_w = b.shape
    p_pad = -(-max(p, 1) // TILE_P) * TILE_P
    a_rows = max(8, -(-a_w // 8) * 8)
    b_rows = max(8, -(-b_w // 8) * 8)

    a_t = jnp.full((a_rows, p_pad), -1, jnp.int32).at[:a_w, :p].set(a.T)
    b_t = jnp.full((b_rows, p_pad), -1, jnp.int32).at[:b_w, :p].set(b.T)

    out = pl.pallas_call(
        _kernel,
        grid=(p_pad // TILE_P,),
        in_specs=[
            pl.BlockSpec((a_rows, TILE_P), lambda i: (0, i)),
            pl.BlockSpec((b_rows, TILE_P), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((8, TILE_P), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, p_pad), jnp.int32),
        interpret=interpret,
    )(a_t, b_t)
    return out[0, :p]

"""Fused sort-merge join pipeline: pack -> sort -> probe -> expand in ONE
XLA dispatch.

The staged join path (matching._join_sorted) runs ~5 device dispatches per
join — key packing, two side sorts, the merge probe, the segment expand —
and syncs the full per-row count vector to host between probe and expand.
The fused entry points here trace the whole chain into a single jitted
computation, so the match-range arrays (start/cnt) never round-trip
through host memory between stages and only ONE scalar (the match total)
is synced per join:

  sort_probe_expand   the full chain at a known output capacity (the
                      planner pre-sizes joins from cardinality
                      estimates).  The sorted sides and match ranges are
                      returned as device-resident byproducts so the
                      CapacityOverflow retry contract is preserved: on
                      overflow the caller re-runs ONLY the expand.
  sort_probe          pack+sort+probe when the capacity is not known up
                      front; the caller syncs the total, sizes the
                      output, and dispatches the expand separately.
  pack_keys           the fused dense-rank key packing alone, for the
                      staged path (sorted-run reuse, resume replays):
                      ONE lexsort over all shared columns replaces the
                      seed's per-column rank/pack chain (S-1 lexsorts),
                      and single-column keys take an identity path with
                      no concat/split device ops at all.
  lexsort_distinct    the fused projection+lexsort+distinct-mask+count
                      used by matching.dedup_project, so reach-join
                      dedup rides the same fused sort primitive.

Multi-column joins exploit a structural win the staged path cannot: the
ONE lexsort over the concatenated sides yields the dense-rank keys AND
both sides' sorted orders (stable sort => filtering the combined order by
side preserves each side's order), so pack + sort(A) + sort(B) collapse
into a single sort of A+B rows.

Probe impls mirror kernels.ops.merge_probe ('sorted' searchsorted /
'ref' oracle on CPU, Pallas kernel under 'pallas'/'interpret'); under the
Pallas impls the segment-offset expand uses `expand_segments_pallas`, a
merge_probe-style block-skipping counting kernel that replaces the
output-side searchsorted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref as _ref
from .merge_probe import merge_probe_pallas

# Join-key space (shared with core.matching): real packed keys live in
# [0, 2^31 - 3]; the top two int32 values are invalid-row sentinels,
# distinct per side so an invalid a-row never matches an invalid b-row.
A_INVALID = (1 << 31) - 1
B_INVALID = (1 << 31) - 2

_I32_MAX = jnp.iinfo(jnp.int32).max


# ------------------------- fused dense-rank pack ----------------------- #
def _side_cols(rows, sel, valid, sentinel):
    return tuple(jnp.where(valid, rows[:, s], sentinel).astype(jnp.int32)
                 for s in sel)


def _ranks_sorted(sorted_cols):
    """Dense ranks of lexicographically sorted column tuples: rank
    increments exactly at rows that differ from their predecessor."""
    boundary = jnp.zeros((sorted_cols[0].shape[0] - 1,), bool)
    for c in sorted_cols:
        boundary |= c[1:] != c[:-1]
    new = jnp.concatenate([jnp.ones((1,), jnp.int32),
                           boundary.astype(jnp.int32)])
    return jnp.cumsum(new) - 1


@functools.partial(jax.jit, static_argnames=("a_sel", "b_sel"))
def pack_keys(a_rows, b_rows, a_sel, b_sel):
    """Pack the shared join columns of both tables into one int32 key per
    row (original row order).  Single shared column: the node id IS the
    key — identity path, no concatenate/split dispatches.  Multiple
    columns: ONE lexsort over the concatenated sides assigns dense ranks
    to the full column tuple (order- and equality-preserving, so equal
    keys <=> equal tuples and any number of columns fits 31 bits)."""
    n_a = a_rows.shape[0]
    a_valid = a_rows[:, 0] >= 0
    b_valid = b_rows[:, 0] >= 0
    if len(a_sel) == 1:
        a_keys = jnp.where(a_valid, a_rows[:, a_sel[0]],
                           A_INVALID).astype(jnp.int32)
        b_keys = jnp.where(b_valid, b_rows[:, b_sel[0]],
                           B_INVALID).astype(jnp.int32)
        return a_keys, b_keys
    cols = tuple(jnp.concatenate([va, vb]) for va, vb in zip(
        _side_cols(a_rows, a_sel, a_valid, A_INVALID),
        _side_cols(b_rows, b_sel, b_valid, B_INVALID)))
    order = jnp.lexsort(tuple(reversed(cols)))
    ranks = _ranks_sorted(tuple(c[order] for c in cols))
    key = jnp.zeros_like(ranks).at[order].set(ranks).astype(jnp.int32)
    a_keys = jnp.where(a_valid, key[:n_a], A_INVALID)
    b_keys = jnp.where(b_valid, key[n_a:], B_INVALID)
    return a_keys, b_keys


# --------------------------- fused side sort --------------------------- #
def _sort_sides(a_rows, b_rows, a_sel, b_sel):
    """(a_keys_s, a_rows_s, b_keys_s, b_rows_s), both sides sorted by the
    packed key.  Single column: identity keys, one argsort per side.
    Multiple columns: the pack lexsort is REUSED as the sort — the stable
    combined order, filtered by side, is each side's sorted order."""
    n_a, n_b = a_rows.shape[0], b_rows.shape[0]
    a_valid = a_rows[:, 0] >= 0
    b_valid = b_rows[:, 0] >= 0
    if len(a_sel) == 1:
        a_keys = jnp.where(a_valid, a_rows[:, a_sel[0]],
                           A_INVALID).astype(jnp.int32)
        b_keys = jnp.where(b_valid, b_rows[:, b_sel[0]],
                           B_INVALID).astype(jnp.int32)
        ao = jnp.argsort(a_keys)
        bo = jnp.argsort(b_keys)
        return a_keys[ao], a_rows[ao], b_keys[bo], b_rows[bo]
    cols = tuple(jnp.concatenate([va, vb]) for va, vb in zip(
        _side_cols(a_rows, a_sel, a_valid, A_INVALID),
        _side_cols(b_rows, b_sel, b_valid, B_INVALID)))
    order = jnp.lexsort(tuple(reversed(cols)))
    key_sorted = _ranks_sorted(tuple(c[order] for c in cols)).astype(
        jnp.int32)
    from_a = order < n_a
    ia = jnp.nonzero(from_a, size=n_a)[0]           # exactly n_a entries
    ib = jnp.nonzero(~from_a, size=n_b)[0]
    return (key_sorted[ia], a_rows[order[ia]],
            key_sorted[ib], b_rows[order[ib] - n_a])


def _probe(a_keys_s, b_keys_s, probe: str):
    if probe == "sorted":
        return _ref.merge_probe_sorted(a_keys_s, b_keys_s)
    if probe == "ref":
        return _ref.merge_probe_ref(a_keys_s, b_keys_s)
    return merge_probe_pallas(a_keys_s, b_keys_s,
                              interpret=(probe == "interpret"))


# ----------------- segment-offset expand (Pallas seg) ------------------ #
SEG_TILE_R = 8              # sublane rows per output tile -> 8*128 slots
SEG_ROWS = 8                # lane rows per csum block: one (8, 128) tile
SEG_BLOCK = SEG_ROWS * 128  # csum entries per block


def _seg_kernel(csum_ref, seg_ref):
    """seg[t] = #{i : csum[i] <= t} == searchsorted(csum, t, 'right').

    Same block-skipping accumulation as merge_probe: csum is
    nondecreasing, so a csum block entirely <= the tile's smallest t
    contributes its full width, a block entirely > the largest t
    contributes nothing, and a boundary block repeats the test per
    128-entry row so only overlapping rows run the lane loop."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        seg_ref[...] = jnp.zeros_like(seg_ref)

    t0 = pl.program_id(0) * (SEG_TILE_R * 128)
    t_max = t0 + SEG_TILE_R * 128 - 1
    r = jax.lax.broadcasted_iota(jnp.int32, seg_ref.shape, 0)
    l = jax.lax.broadcasted_iota(jnp.int32, seg_ref.shape, 1)
    t = t0 + r * 128 + l
    c = csum_ref[...]                           # [SEG_ROWS, 128]
    below = c[SEG_ROWS - 1, 127] <= t0          # block counts for every t
    above = c[0, 0] > t_max

    @pl.when(below)
    def _all_below():
        seg_ref[...] += jnp.full(seg_ref.shape, SEG_BLOCK, jnp.int32)

    @pl.when(jnp.logical_not(below | above))
    def _overlap():
        def row(i, carry):
            cr = csum_ref[pl.ds(i, 1), :]       # [1, 128]
            r_below = cr[0, 127] <= t0
            r_above = cr[0, 0] > t_max

            @pl.when(r_below)
            def _row_below():
                seg_ref[...] += jnp.full(seg_ref.shape, 128, jnp.int32)

            @pl.when(jnp.logical_not(r_below | r_above))
            def _row_overlap():
                acc = jnp.zeros(seg_ref.shape, jnp.int32)
                for j in range(128):
                    acc += (cr[0, j] <= t).astype(jnp.int32)
                seg_ref[...] += acc
            return carry

        jax.lax.fori_loop(0, SEG_ROWS, row, 0)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def expand_segments_pallas(csum, cap: int, interpret: bool = False):
    """Segment index of every output slot t in [0, cap): the sorted a-row
    whose cumulative match-count range contains t."""
    n = csum.shape[0]
    span = SEG_TILE_R * 128
    cap_pad = -(-max(cap, 1) // span) * span
    n_pad = -(-max(n, 1) // SEG_BLOCK) * SEG_BLOCK
    # padding with INT32_MAX never counts: csum values are < 2^31 totals
    c_p = jnp.full((n_pad,), _I32_MAX, jnp.int32).at[:n].set(
        csum.astype(jnp.int32))
    c_m = c_p.reshape(n_pad // 128, 128)
    grid = (cap_pad // span, n_pad // SEG_BLOCK)
    seg = pl.pallas_call(
        _seg_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((SEG_ROWS, 128), lambda i, k: (k, 0))],
        out_specs=pl.BlockSpec((SEG_TILE_R, 128), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cap_pad // 128, 128), jnp.int32),
        interpret=interpret,
    )(c_m)
    return seg.reshape(-1)[:cap]


def _expand(a_rows_s, b_rows_s, start, cnt, limit, cap, new_sel, has_new,
            probe):
    """Segment-offset expansion of (start, cnt) match ranges — the fused
    in-jit twin of matching._merge_expand, returning the match total as a
    device scalar byproduct."""
    a_cap = a_rows_s.shape[0]
    csum = jnp.cumsum(cnt)
    total = csum[a_cap - 1]
    if probe in ("pallas", "interpret"):
        seg = expand_segments_pallas(csum, cap,
                                     interpret=(probe == "interpret"))
    else:
        t_idx = jnp.arange(cap, dtype=jnp.int32)
        seg = jnp.searchsorted(csum, t_idx, side="right").astype(jnp.int32)
    t = jnp.arange(cap, dtype=jnp.int32)
    valid = (t < total) & (t < limit)
    i = jnp.minimum(seg, a_cap - 1)
    base = csum[i] - cnt[i]
    # offset as t - base (subtraction form), never a fused int32
    # remainder: see matching._cross_expand's XLA-CPU miscompile note
    j = jnp.clip(start[i] + (t - base), 0, b_rows_s.shape[0] - 1)
    left = jnp.where(valid[:, None], a_rows_s[i], -1)
    if has_new:
        sel = jnp.asarray(new_sel, jnp.int32)
        right = jnp.where(valid[:, None], b_rows_s[j][:, sel], -1)
        return jnp.concatenate([left, right], axis=1), total
    return left, total


# --------------------------- fused entry points ------------------------ #
@functools.partial(jax.jit, static_argnames=("a_sel", "b_sel", "cap",
                                             "new_sel", "has_new", "probe"))
def sort_probe_expand(a_rows, b_rows, limit, *, a_sel, b_sel, cap,
                      new_sel, has_new, probe):
    """The full fused join chain at a known output capacity.

    Returns (rows, total, a_keys_s, a_rows_s, b_keys_s, b_rows_s, start,
    cnt): the output rows plus the device-resident sorted sides and match
    ranges, so the caller can cache sorted runs and — on capacity
    overflow — retry ONLY the expand at the exact size.  `limit` is a
    traced scalar (row-limit truncation without recompiles).  Caller
    contract: |A|*|B| < 2^31 so the total fits the int32 device scalar
    (larger joins stay on the staged path with its int64 host sum)."""
    a_keys_s, a_rows_s, b_keys_s, b_rows_s = _sort_sides(
        a_rows, b_rows, a_sel, b_sel)
    start, cnt = _probe(a_keys_s, b_keys_s, probe)
    rows, total = _expand(a_rows_s, b_rows_s, start, cnt, limit, cap,
                          new_sel, has_new, probe)
    return rows, total, a_keys_s, a_rows_s, b_keys_s, b_rows_s, start, cnt


@functools.partial(jax.jit, static_argnames=("a_sel", "b_sel", "probe"))
def sort_probe(a_rows, b_rows, *, a_sel, b_sel, probe):
    """Fused pack+sort+probe for joins with no capacity hint: the caller
    syncs the int32 total, sizes the output, and expands separately.
    Same |A|*|B| < 2^31 caller contract as sort_probe_expand."""
    a_keys_s, a_rows_s, b_keys_s, b_rows_s = _sort_sides(
        a_rows, b_rows, a_sel, b_sel)
    start, cnt = _probe(a_keys_s, b_keys_s, probe)
    total = jnp.sum(cnt)
    return a_keys_s, a_rows_s, b_keys_s, b_rows_s, start, cnt, total


# ------------------------ fused sort-distinct -------------------------- #
@functools.partial(jax.jit, static_argnames=("sel",))
def lexsort_distinct(rows, sel):
    """Fused projection + lexsort + first-of-group mask + count for
    dedup_project: (sorted projection, keep mask, kept count) in one
    dispatch.  Invalid rows map every projected value to the a-side
    sentinel, so they sort last and are masked out."""
    valid = rows[:, 0] >= 0
    cols = _side_cols(rows, sel, valid, A_INVALID)
    order = jnp.lexsort(tuple(reversed(cols)))
    proj = jnp.stack(cols, axis=1)[order]
    keep = _ref.distinct_mask_sorted(proj) & (proj[:, 0] != A_INVALID)
    return proj, keep, jnp.sum(keep.astype(jnp.int32))

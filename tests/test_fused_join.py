"""Fused join pipeline + radix hash join: kernel-level parity against
the staged sort-merge path and a brute-force oracle, the single-column
identity key path, interpret-mode Pallas parity, overflow-resume
contracts for both pipelines, and warm-replay strategy pinning."""
import numpy as np
import jax.numpy as jnp
import pytest

import repro.kernels.fused_join as kfused
import repro.kernels.radix_join as krad
import repro.kernels.ops as kops
import repro.core.matching as matching
from repro.core.matching import (
    Table, CapacityOverflow, JoinTelemetry, join_tables, planned_join,
    dedup_project, _pow2,
)
from repro.core.planner import CapEstimate

RNG = np.random.default_rng(7)


def mk_table(cols, data):
    data = np.asarray(data, np.int32).reshape(-1, len(cols))
    cap = _pow2(len(data))
    rows = np.full((cap, len(cols)), -1, np.int32)
    rows[: len(data)] = data
    return Table(cols=tuple(cols), rows=jnp.asarray(rows), count=len(data))


def oracle_join(a, b):
    shared = [c for c in a.cols if c in b.cols]
    new = [j for j, c in enumerate(b.cols) if c not in a.cols]
    out = []
    for ra in a.numpy():
        for rb in b.numpy():
            if all(ra[a.cols.index(c)] == rb[b.cols.index(c)]
                   for c in shared):
                out.append(tuple(int(x) for x in ra)
                           + tuple(int(rb[j]) for j in new))
    return sorted(out)


def rows_multiset(t):
    return sorted(tuple(int(x) for x in r) for r in t.numpy())


def rand_pair(seed, na=60, nb=60, ncols_a=2, ncols_b=2, vmax=5):
    rng = np.random.default_rng(seed)
    a_cols = tuple(rng.choice(5, ncols_a, replace=False))
    b_cols = tuple(rng.choice(5, ncols_b, replace=False))
    a = mk_table(a_cols, rng.integers(0, vmax, (na, ncols_a)))
    b = mk_table(b_cols, rng.integers(0, vmax, (nb, ncols_b)))
    return a, b


# --------------------------- pack_keys -------------------------------- #
def test_pack_keys_multi_col_dense_rank_oracle():
    rng = np.random.default_rng(2)
    a = mk_table((0, 1), rng.integers(0, 4, (50, 2)))
    b = mk_table((0, 1), rng.integers(0, 4, (40, 2)))
    ak, bk = kfused.pack_keys(a.rows, b.rows, (0, 1), (0, 1))
    ak, bk = np.asarray(ak), np.asarray(bk)
    a_np, b_np = np.asarray(a.rows), np.asarray(b.rows)
    # keys agree with tuple equality across AND within sides
    for i in range(a.count):
        for j in range(b.count):
            same = bool((a_np[i] == b_np[j]).all())
            assert (ak[i] == bk[j]) == same
        for i2 in range(a.count):
            assert (ak[i] == ak[i2]) == bool((a_np[i] == a_np[i2]).all())
    # keys are order-preserving on the tuples
    pairs = sorted((tuple(a_np[i]), ak[i]) for i in range(a.count))
    ks = [k for _, k in pairs]
    assert ks == sorted(ks)
    # padding rows map to the side sentinels
    assert (ak[a.count:] == kfused.A_INVALID).all()
    assert (bk[b.count:] == kfused.B_INVALID).all()


def test_pack_keys_single_col_identity():
    """Single shared column skips dense-rank packing: keys ARE the
    column values (valid rows), so no lexsort dispatch happens at all."""
    a = mk_table((0, 1), [[i % 7, i] for i in range(30)])
    b = mk_table((0, 2), [[i % 7, i + 100] for i in range(20)])
    ak, bk = kfused.pack_keys(a.rows, b.rows, (0,), (0,))
    assert (np.asarray(ak)[: a.count] == np.asarray(a.rows)[: a.count, 0]).all()
    assert (np.asarray(bk)[: b.count] == np.asarray(b.rows)[: b.count, 0]).all()
    assert (np.asarray(ak)[a.count:] == kfused.A_INVALID).all()
    assert (np.asarray(bk)[b.count:] == kfused.B_INVALID).all()


# --------------------- fused chain vs staged path --------------------- #
@pytest.mark.parametrize("seed", range(6))
def test_fused_equals_unfused_equals_oracle(seed):
    a, b = rand_pair(seed, ncols_a=(seed % 3) + 1, ncols_b=2)
    want = oracle_join(a, b)
    fused = join_tables(a, b, impl="sorted", fuse=True)
    staged = join_tables(a, b, impl="sorted", fuse=False)
    assert rows_multiset(fused) == want
    assert rows_multiset(staged) == want


@pytest.mark.parametrize("probe", ["sorted", "ref", "interpret"])
def test_sort_probe_expand_probe_impl_parity(probe):
    a, b = rand_pair(11, ncols_a=2, ncols_b=2, vmax=4)
    want = oracle_join(a, b)
    got = join_tables(a, b, impl="sorted", probe_impl=probe, fuse=True)
    assert rows_multiset(got) == want


@pytest.mark.parametrize("n,cap", [(17, 256), (200, 1024), (1, 64),
                                   (1000, 8192), (5000, 40000)])
def test_expand_segments_pallas_matches_searchsorted(n, cap):
    rng = np.random.default_rng(5)
    cnt = rng.integers(0, 9, n).astype(np.int32)
    csum = np.cumsum(cnt).astype(np.int32)
    seg = np.asarray(kfused.expand_segments_pallas(
        jnp.asarray(csum), cap, interpret=True))
    t = np.arange(cap)
    want = np.searchsorted(csum, t, side="right").astype(np.int32)
    assert (seg == want).all(), (n, cap)


def test_fused_overflow_resume_skips_resort():
    """CapacityOverflow from the fused chain carries a _ProbeResume; the
    retry replays it without re-sorting (telemetry counts 2 sorts for the
    whole planned_join, not 4)."""
    rng = np.random.default_rng(9)
    a = mk_table((0, 1), rng.integers(0, 3, (64, 2)))
    b = mk_table((1, 2), rng.integers(0, 3, (64, 2)))
    want = oracle_join(a, b)
    assert len(want) > 16
    tel = JoinTelemetry()
    with pytest.raises(CapacityOverflow) as ei:
        join_tables(a, b, impl="sorted", cap=16, fuse=True, telemetry=tel)
    resume = getattr(ei.value, "resume", None)
    assert isinstance(resume, matching._ProbeResume)
    out = join_tables(a, b, impl="sorted", cap=_pow2(ei.value.needed),
                      _resume=resume, fuse=True, telemetry=tel)
    assert rows_multiset(out) == want
    assert tel.sorts_performed == 2        # resume did not re-sort


def test_fused_row_limit_truncation():
    a = mk_table((0,), [[i % 4] for i in range(40)])
    b = mk_table((0, 1), [[i % 4, i] for i in range(40)])
    full = join_tables(a, b, impl="sorted", fuse=True)
    lim = join_tables(a, b, impl="sorted", fuse=True, row_limit=17)
    assert full.count > 17 and lim.count == 17 and lim.truncated
    assert set(rows_multiset(lim)) <= set(rows_multiset(full))


# ------------------------------ radix --------------------------------- #
@pytest.mark.parametrize("seed", range(6))
def test_radix_equals_oracle(seed):
    a, b = rand_pair(seed + 100, ncols_a=2, ncols_b=2, vmax=6)
    want = oracle_join(a, b)
    got = join_tables(a, b, impl="radix")
    assert rows_multiset(got) == want


def test_radix_partition_window_probe_roundtrip():
    rng = np.random.default_rng(13)
    b_keys = jnp.asarray(
        np.concatenate([rng.integers(0, 50, 90),
                        np.full(38, kfused.B_INVALID)]).astype(np.int32))
    b_rows = jnp.asarray(rng.integers(0, 99, (128, 2)).astype(np.int32))
    bits = 5
    keys_p, rows_p, edges, maxlen = krad.radix_partition(b_keys, b_rows, bits)
    edges = np.asarray(edges)
    assert edges[0] == 0 and edges[-1] <= 128
    # every real bucket's slice hashes to that bucket AND is key-sorted
    # (the contiguous-match-run invariant the probe and assembly rely on)
    kp = np.asarray(keys_p)
    for bkt in range(1 << bits):
        sl = kp[edges[bkt]: edges[bkt + 1]]
        if sl.size:
            h = (sl.astype(np.uint32) * np.uint32(2654435761)) >> (32 - bits)
            assert (h == bkt).all()
            assert (np.diff(sl) >= 0).all()
    assert int(maxlen) == max(
        edges[b + 1] - edges[b] for b in range(1 << bits))
    a_keys = jnp.asarray(rng.integers(0, 50, 64).astype(np.int32))
    lmax = _pow2(int(maxlen), lo=8)
    win_keys, win_start = krad.radix_window(a_keys, edges, keys_p, bits, lmax)
    lt, cnt = krad.window_probe_ref(a_keys, win_keys)
    bk_np = np.asarray(b_keys)[:90]
    want_cnt = np.array([(bk_np == int(k)).sum() for k in a_keys])
    assert (np.asarray(cnt) == want_cnt).all()
    # lt + win_start locates each key's match run in the partition
    lt_np, ws_np = np.asarray(lt), np.asarray(win_start)
    for r, k in enumerate(np.asarray(a_keys)):
        if want_cnt[r]:
            run = kp[ws_np[r] + lt_np[r]: ws_np[r] + lt_np[r] + want_cnt[r]]
            assert (run == k).all()


def test_radix_probe_interpret_matches_ref():
    rng = np.random.default_rng(17)
    a_keys = jnp.asarray(rng.integers(0, 9, 40).astype(np.int32))
    win = jnp.asarray(np.sort(rng.integers(0, 9, (40, 16)), axis=1)
                      .astype(np.int32))
    r_lt, r_cnt = kops.radix_probe(a_keys, win, impl="ref")
    i_lt, i_cnt = kops.radix_probe(a_keys, win, impl="interpret")
    assert (np.asarray(r_lt) == np.asarray(i_lt)).all()
    assert (np.asarray(r_cnt) == np.asarray(i_cnt)).all()


def test_radix_overflow_resume():
    rng = np.random.default_rng(19)
    a = mk_table((0, 1), rng.integers(0, 4, (80, 2)))
    b = mk_table((1, 2), rng.integers(0, 4, (80, 2)))
    want = oracle_join(a, b)
    assert len(want) > 32
    with pytest.raises(CapacityOverflow) as ei:
        join_tables(a, b, impl="radix", cap=32)
    resume = getattr(ei.value, "resume", None)
    assert isinstance(resume, matching._RadixResume)
    out = join_tables(a, b, impl="radix", cap=_pow2(ei.value.needed),
                      _resume=resume)
    assert rows_multiset(out) == want


def test_radix_row_limit_and_order_preserved():
    a = mk_table((0, 1), [[i % 5, i] for i in range(50)])
    b = mk_table((0, 2), [[i % 5, i + 100] for i in range(30)])
    out = join_tables(a, b, impl="radix")
    # output preserves A's row order (radix never sorts the probe side)
    a_col1 = [r[1] for r in
              (tuple(int(x) for x in row) for row in out.numpy())]
    assert a_col1 == sorted(a_col1)
    lim = join_tables(a, b, impl="radix", row_limit=23)
    assert lim.count == 23 and lim.truncated


def test_radix_skew_falls_back_to_sorted_deterministically():
    """A hot key inflating the widest bucket past RADIX_WORK_MAX must
    fall back to sort-merge — both attempts, same answer."""
    hot = np.zeros((5000, 2), np.int32)          # all rows share key 0
    hot[:, 1] = np.arange(5000)
    a = mk_table((0, 1), hot)
    b = mk_table((0, 2), hot.copy())
    old = matching.RADIX_WORK_MAX
    matching.RADIX_WORK_MAX = 1                  # force the skew guard
    try:
        r1 = join_tables(a, b, impl="radix", row_limit=100)
        r2 = join_tables(a, b, impl="radix", row_limit=100)
    finally:
        matching.RADIX_WORK_MAX = old
    assert r1.count == r2.count == 100
    assert rows_multiset(r1) == rows_multiset(r2)


def test_radix_empty_tables():
    a = mk_table((0, 1), np.zeros((0, 2), np.int32))
    b = mk_table((0, 2), [[1, 2]])
    assert join_tables(a, b, impl="radix").count == 0
    assert join_tables(b, a, impl="radix").count == 0


# --------------------- three-strategy identity ------------------------ #
@pytest.mark.parametrize("seed", range(4))
def test_nested_sorted_radix_identity(seed):
    a, b = rand_pair(seed + 300, na=70, nb=50,
                     ncols_a=(seed % 2) + 1, ncols_b=2, vmax=4)
    r = {impl: rows_multiset(join_tables(a, b, impl=impl))
         for impl in ("nested", "sorted", "radix")}
    assert r["nested"] == r["sorted"] == r["radix"] == oracle_join(a, b)


# ------------------------ dedup_project fusion ------------------------ #
def test_dedup_project_fused_parity():
    rng = np.random.default_rng(23)
    t = mk_table((3, 1, 7), rng.integers(0, 4, (60, 3)))
    out = dedup_project(t, (7, 1))
    want = sorted({(int(r[2]), int(r[1])) for r in t.numpy()})
    assert rows_multiset(out) == want
    assert out.sort_order == (7, 1)


def test_lexsort_distinct_tolerates_scattered_valid_rows():
    """Valid rows may sit anywhere in the capacity, not just a prefix."""
    rows = np.full((16, 2), -1, np.int32)
    rows[3] = [2, 9]
    rows[7] = [1, 5]
    rows[12] = [2, 9]                            # duplicate
    t = Table(cols=(0, 1), rows=jnp.asarray(rows), count=3)
    out = dedup_project(t, (0, 1))
    assert rows_multiset(out) == [(1, 5), (2, 9)]


# ---------------------- warm-replay strategy pin ---------------------- #
def test_planned_join_cap_estimate_pins_impl():
    rng = np.random.default_rng(29)
    a = mk_table((0, 1), rng.integers(0, 6, (60, 2)))
    b = mk_table((1, 2), rng.integers(0, 6, (60, 2)))
    recorded = []
    rec = lambda *r: recorded.append(r)
    base = planned_join(a, b, est=700, impl="sorted", record=rec)
    for forced in ("radix", "nested", "sorted"):
        recorded.clear()
        out = planned_join(a, b, CapEstimate(base.count, base.cap, forced),
                           record=rec)
        assert recorded[0][0] == forced          # strategy replayed
        assert out.cap == base.cap               # capacity replayed
        assert rows_multiset(out) == rows_multiset(base)

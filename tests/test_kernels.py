"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes.  Integer kernels -> exact equality."""
import numpy as np
import pytest

from repro.kernels import ops, ref


RNG = np.random.default_rng(42)


def _ragged_sorted_ids(c, b, hi=1000):
    ids = np.full((c, b), -1, np.int32)
    for i in range(c):
        k = RNG.integers(0, b + 1)
        ids[i, :k] = np.sort(RNG.integers(0, hi, k))
    return ids


@pytest.mark.parametrize("c,b,j", [(1, 1, 1), (7, 13, 3), (64, 128, 8),
                                   (130, 70, 5), (256, 257, 16),
                                   (1000, 33, 2)])
def test_interval_count_sweep(c, b, j):
    ids = _ragged_sorted_ids(c, b)
    lo = RNG.integers(0, 900, j).astype(np.int32)
    hi = lo + RNG.integers(0, 200, j).astype(np.int32)
    want = np.asarray(ref.interval_count_ref(ids, lo, hi))
    got = np.asarray(ops.interval_count(ids, lo, hi, impl="interpret"))
    np.testing.assert_array_equal(got, want)


def test_interval_count_empty_interval():
    ids = _ragged_sorted_ids(10, 8)
    lo = np.asarray([5], np.int32)
    hi = np.asarray([5], np.int32)          # empty
    got = np.asarray(ops.interval_count(ids, lo, hi, impl="interpret"))
    assert (got == 0).all()


def test_interval_count_padding_never_counts():
    ids = np.full((4, 16), -1, np.int32)    # all padding
    lo = np.asarray([0], np.int32)
    hi = np.asarray([10 ** 6], np.int32)
    got = np.asarray(ops.interval_count(ids, lo, hi, impl="interpret"))
    assert (got == 0).all()


@pytest.mark.parametrize("c,w", [(1, 1), (9, 3), (64, 8), (200, 17),
                                 (513, 4)])
def test_bitmask_contains_sweep(c, w):
    cand = RNG.integers(0, 2 ** 32, (c, w), dtype=np.uint32)
    q = RNG.integers(0, 2 ** 32, w, dtype=np.uint32)
    want = np.asarray(ref.bitmask_contains_ref(cand, q))
    got = np.asarray(ops.bitmask_contains(cand, q, impl="interpret"))
    np.testing.assert_array_equal(got, want)


def test_bitmask_self_contained():
    cand = RNG.integers(0, 2 ** 32, (16, 4), dtype=np.uint32)
    got = np.asarray(ops.bitmask_contains(cand, cand[3], impl="interpret"))
    assert got[3] == 1


@pytest.mark.parametrize("p,a,b", [(1, 1, 1), (5, 7, 11), (64, 32, 64),
                                   (257, 16, 8), (100, 130, 20)])
def test_intersect_any_sweep(p, a, b):
    x = np.where(RNG.random((p, a)) < 0.7,
                 RNG.integers(0, 50, (p, a)), -1).astype(np.int32)
    y = np.where(RNG.random((p, b)) < 0.7,
                 RNG.integers(0, 50, (p, b)), -1).astype(np.int32)
    want = np.asarray(ref.intersect_any_ref(x, y))
    got = np.asarray(ops.intersect_any(x, y, impl="interpret"))
    np.testing.assert_array_equal(got, want)


def test_intersect_padding_not_a_hit():
    x = np.full((3, 4), -1, np.int32)
    y = np.full((3, 4), -1, np.int32)
    got = np.asarray(ops.intersect_any(x, y, impl="interpret"))
    assert (got == 0).all()


def _sorted_keys(n, hi=500, sentinel=None, frac_pad=0.2):
    ks = RNG.integers(0, hi, n).astype(np.int32)
    if sentinel is not None and n:
        ks[: max(int(n * frac_pad), 1)] = sentinel
    return np.sort(ks)


@pytest.mark.parametrize("na,nb", [(1, 1), (7, 130), (128, 128),
                                   (300, 77), (1000, 513), (257, 8),
                                   (1000, 5000), (5000, 1000)])
def test_merge_probe_sweep(na, nb):
    a = _sorted_keys(na, sentinel=(1 << 31) - 1)       # a-side invalid pads
    b = _sorted_keys(nb, sentinel=(1 << 31) - 2)       # b-side invalid pads
    ws, wc = (np.asarray(x) for x in ref.merge_probe_ref(a, b))
    for impl in ("sorted", "interpret"):
        gs, gc = (np.asarray(x) for x in ops.merge_probe(a, b, impl=impl))
        np.testing.assert_array_equal(gs, ws)
        np.testing.assert_array_equal(gc, wc)


def test_merge_probe_ranges_are_consistent():
    """start/cnt must delimit exactly the equal-key run in b."""
    a = _sorted_keys(64, hi=30)
    b = _sorted_keys(96, hi=30)
    s, c = (np.asarray(x) for x in ops.merge_probe(a, b, impl="interpret"))
    for i, key in enumerate(a):
        np.testing.assert_array_equal(b[s[i]: s[i] + c[i]],
                                      np.full(c[i], key))
        assert s[i] == np.searchsorted(b, key, side="left")


def test_merge_probe_invalid_rows_never_match():
    """The join's per-side sentinels must produce zero-count ranges."""
    a = np.sort(np.asarray([3, 7, (1 << 31) - 1] * 4, np.int32))
    b = np.sort(np.asarray([7, 9, (1 << 31) - 2] * 4, np.int32))
    for impl in ("sorted", "interpret"):
        _, c = (np.asarray(x) for x in ops.merge_probe(a, b, impl=impl))
        assert (c[a == (1 << 31) - 1] == 0).all()
        assert (c[a == 7] == 4).all()


def test_auto_dispatch_cpu_is_ref():
    ids = _ragged_sorted_ids(8, 8)
    lo = np.asarray([0], np.int32)
    hi = np.asarray([100], np.int32)
    a = np.asarray(ops.interval_count(ids, lo, hi, impl="auto"))
    b = np.asarray(ops.interval_count(ids, lo, hi, impl="ref"))
    np.testing.assert_array_equal(a, b)

"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel body on the CPU but never applies the TPU
lowering rules (block shapes tiled to (8, 128), fast-memory limits), so
every kernel that `impl="auto"` selects on a TPU is compiled here for one
chip of a described v5e at the widths the serving path uses: at least
65,536 keys per join side.  Nothing runs; a compile that passes also
means the kernel's blocks fit the chip's scoped VMEM.

The topology is described inside a fixture (never at import) because
only one process at a time may load the TPU library, and several test
workers import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.fused_join as kfused
from repro.kernels.bitmask_contains import bitmask_contains_pallas
from repro.kernels.interval_count import interval_count_pallas
from repro.kernels.merge_probe import merge_probe_pallas
from repro.kernels.radix_join import window_probe_pallas
from repro.kernels.sorted_intersect import intersect_any_pallas
from repro.launch.compile_cache import persistent_cache_off

N = 65_536                  # join keys per side


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    with persistent_cache_off():
        yield SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_probe_compiles(one_chip):
    _assert_kernel_compiles(merge_probe_pallas, _spec(one_chip, (N,)),
                            _spec(one_chip, (N,)))


def test_expand_segments_compiles(one_chip):
    _assert_kernel_compiles(
        lambda csum: kfused.expand_segments_pallas(csum, 4 * N),
        _spec(one_chip, (N,)))


def test_window_probe_compiles(one_chip):
    _assert_kernel_compiles(window_probe_pallas, _spec(one_chip, (N,)),
                            _spec(one_chip, (N, 16)))


def test_intersect_any_compiles(one_chip):
    # reach sets are probed 1,024 pairs at a time, each list at most
    # 4,096 ids wide (connectivity.reach_sets' cap)
    _assert_kernel_compiles(intersect_any_pallas,
                            _spec(one_chip, (1024, 4096)),
                            _spec(one_chip, (1024, 4096)))


def test_interval_count_compiles(one_chip):
    _assert_kernel_compiles(interval_count_pallas,
                            _spec(one_chip, (N, 128)),
                            _spec(one_chip, (4,)), _spec(one_chip, (4,)))


def test_bitmask_contains_compiles(one_chip):
    _assert_kernel_compiles(bitmask_contains_pallas,
                            _spec(one_chip, (N, 4), jnp.uint32),
                            _spec(one_chip, (4,), jnp.uint32))


@pytest.mark.parametrize("sel", [(0,), (0, 1)], ids=["one_col", "two_col"])
def test_fused_sort_probe_expand_compiles(one_chip, sel):
    def join(a_rows, b_rows, limit):
        return kfused.sort_probe_expand(
            a_rows, b_rows, limit, a_sel=sel, b_sel=sel, cap=2 * N,
            new_sel=(2, 3), has_new=True, probe="pallas")

    _assert_kernel_compiles(join, _spec(one_chip, (N, 4)),
                            _spec(one_chip, (N, 4)), _spec(one_chip, ()))

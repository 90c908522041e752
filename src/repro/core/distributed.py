"""Distributed execution of the RDF-ℏ check phase (shard_map).

Graph partitioning: node rows of each NI entry are range-partitioned
across the 'data' mesh axis; every device evaluates the neighborhood
check for its own node range (embarrassingly parallel — the paper's
phases only synchronize at join boundaries, where candidate tables are
orders of magnitude smaller than the graph: pruning is what makes the
all_gather cheap).

On the serving mesh the 'pod' axis replicates the index for
query-parallel throughput; `shard_check` only uses 'data'.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from jax import shard_map

from ..kernels import ref as kref


def pad_rows(arr: np.ndarray, ndev: int, fill) -> np.ndarray:
    n = arr.shape[0]
    npad = (-n) % ndev
    if npad == 0:
        return arr
    pad_shape = (npad,) + arr.shape[1:]
    return np.concatenate([arr, np.full(pad_shape, fill, arr.dtype)], 0)


def shard_check(mesh: Mesh, ids: np.ndarray, lo: np.ndarray,
                hi: np.ndarray, need: np.ndarray,
                overflow: np.ndarray) -> np.ndarray:
    """Distributed single-distance neighborhood check.

    ids [N, cap] per-node neighbor ids (-1 padded), sharded by node row
    over the 'data' axis.  lo/hi/need [J]: required intervals and counts
    (replicated).  overflow [N]: auto-pass bits.  Returns pass mask [N].
    """
    ndev = mesh.devices.size // (mesh.shape.get("model", 1)
                                 * mesh.shape.get("pod", 1))
    n = ids.shape[0]
    ids_p = pad_rows(ids.astype(np.int32), ndev, -1)
    of_p = pad_rows(overflow.astype(np.bool_), ndev, True)

    data_spec = PS("data")
    rep = PS()

    def local(ids_blk, of_blk, lo_, hi_, need_):
        cnt = kref.interval_count_ref(ids_blk, lo_, hi_)
        ok = (cnt >= need_[None, :]).all(axis=1)
        return ok | of_blk

    fn = shard_map(local, mesh=mesh,
                   in_specs=(data_spec, data_spec, rep, rep, rep),
                   out_specs=data_spec)
    with mesh:
        dev_ids = jax.device_put(ids_p, NamedSharding(mesh, data_spec))
        dev_of = jax.device_put(of_p, NamedSharding(mesh, data_spec))
        out = fn(dev_ids, dev_of,
                 jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                 jnp.asarray(need, jnp.int32))
    return np.asarray(out)[:n]


def gather_candidates(mesh: Mesh, mask: np.ndarray, cap: int) -> np.ndarray:
    """all_gather the (compact) candidate ids from every shard.

    Demonstrates the join-boundary collective: each shard compacts its
    local pass mask to <= cap ids, then all_gathers — total bytes are
    O(pruned candidates), not O(N)."""
    ndev = mesh.shape["data"]
    n = mask.shape[0]
    mask_p = pad_rows(mask.astype(np.bool_), ndev, False)

    def local(m_blk):
        ids = jnp.nonzero(m_blk, size=cap, fill_value=-1)[0]
        base = jax.lax.axis_index("data") * m_blk.shape[0]
        ids = jnp.where(ids >= 0, ids + base, -1)
        return jax.lax.all_gather(ids, "data").reshape(-1)

    fn = shard_map(local, mesh=mesh, in_specs=(PS("data"),),
                   out_specs=PS(), check_vma=False)
    with mesh:
        dev = jax.device_put(mask_p, NamedSharding(mesh, PS("data")))
        out = np.asarray(fn(dev))
    return out[out >= 0]

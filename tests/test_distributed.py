"""Distributed-correctness tests.

These need >1 device, so they run a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
must keep seeing one device)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_sub(code: str) -> dict:
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # forced host devices only exist on the CPU
                              # backend; without this each subprocess stalls
                              # for minutes probing for a TPU
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_shard_check_matches_single_device():
    r = run_sub("""
    import json, numpy as np, jax
    from repro.core import build_ni_index
    from jax.sharding import AxisType
    from repro.core.distributed import shard_check
    from repro.kernels import ref as kref
    from repro.data import random_graph
    g = random_graph(n_nodes=100, n_edges=300, seed=5)
    ni = build_ni_index(g, d_max=1)
    e = ni.entries[1]
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    lo = np.asarray([0, 40], np.int32); hi = np.asarray([30, 90], np.int32)
    need = np.asarray([1, 1], np.int32)
    got = shard_check(mesh, e.ids, lo, hi, need, e.overflow)
    import jax.numpy as jnp
    cnt = np.asarray(kref.interval_count_ref(jnp.asarray(e.ids), jnp.asarray(lo), jnp.asarray(hi)))
    want = ((cnt >= need[None, :]).all(1)) | e.overflow
    print(json.dumps({"equal": bool((got == want).all())}))
    """)
    assert r["equal"]


def test_gather_candidates_collects_all():
    r = run_sub("""
    import json, numpy as np, jax
    from repro.core.distributed import gather_candidates
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    mask = rng.random(64) < 0.3
    got = gather_candidates(mesh, mask, cap=32)
    want = np.nonzero(mask)[0]
    print(json.dumps({"equal": sorted(got.tolist()) == want.tolist()}))
    """)
    assert r["equal"]


def test_sharded_train_step_matches_single():
    """DP+TP sharded train step == single-device step (same math)."""
    r = run_sub("""
    import json, numpy as np, jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as PS
    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import InputShape, TrainConfig
    from repro.models import api
    from repro.optim import adamw_init

    cfg = reduced_config(ARCHS["stablelm-1.6b"])
    tcfg = TrainConfig(microbatch=1)
    params = api.init_model(cfg, 0)
    batch = api.concrete_batch(cfg, InputShape("s", 32, 4, "train"), seed=2)
    opt = adamw_init(params)

    # single device
    step1 = jax.jit(api.make_train_step(cfg, tcfg, None))
    p1, o1, m1 = step1(params, opt, batch, 0)

    # 4x2 mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pspec = api.model_pspecs(cfg, mesh)
    bspec = api.batch_pspecs(cfg, InputShape("s", 32, 4, "train"), mesh)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, PS))
    with mesh:
        step2 = jax.jit(api.make_train_step(cfg, tcfg, mesh),
                        in_shardings=(ns(pspec), ns(api.opt_pspecs(cfg, mesh)),
                                      ns(bspec), NamedSharding(mesh, PS())))
        p2, o2, m2 = step2(params, opt, batch, 0)
    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    dp = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print(json.dumps({"dloss": dl, "dparam": dp}))
    """)
    assert r["dloss"] < 1e-3, r
    assert r["dparam"] < 5e-3, r


def test_elastic_shrink_and_reshard():
    r = run_sub("""
    import json, numpy as np, jax
    from jax.sharding import AxisType, PartitionSpec as PS
    from repro.runtime import shrink_mesh, reshard
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    small = shrink_mesh(mesh, "pod")
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    t = reshard({"x": x}, small, {"x": PS("data", "model")})
    ok = (np.asarray(t["x"]) == x).all() and small.axis_names == ("data", "model")
    print(json.dumps({"ok": bool(ok)}))
    """)
    assert r["ok"]

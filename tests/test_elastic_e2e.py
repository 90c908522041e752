"""End-to-end elastic recovery: train on a 2x2x2 (pod,data,model) mesh,
checkpoint, lose the pod axis, reshard onto the surviving 2x2 mesh and
continue — losses must continue finite and the restart must replay the
checkpointed step exactly (deterministic pipeline)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def test_elastic_restart_after_pod_loss(tmp_path):
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(f"""
    import json
    import numpy as np, jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as PS
    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import InputShape, TrainConfig
    from repro.models import api
    from repro.optim import adamw_init
    from repro.checkpoint import Checkpointer
    from repro.runtime import shrink_mesh, reshard
    from repro.data.lm_data import TokenPipeline

    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    tcfg = TrainConfig(lr=1e-3, warmup=1, total_steps=20)
    pipe = TokenPipeline(cfg.vocab_size, 32, 8, seed=4)
    ck = Checkpointer({json.dumps(str(tmp_path))})

    def batch(i):
        b = pipe.global_batch_at(i)
        return {{"tokens": b["tokens"], "labels": b["labels"]}}

    def ns(mesh, t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, PS))

    # phase 1: multi-pod mesh (2,2,2)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    params = api.init_model(cfg, 0)
    opt = adamw_init(params)
    with mesh:
        step = jax.jit(api.make_train_step(cfg, tcfg, mesh))
        losses = []
        for i in range(3):
            if i == 2:   # checkpoint BEFORE the step we will replay
                ck.save(2, {{"params": params, "opt": opt}},
                        meta={{"step": 2}}, async_=False)
            params, opt, m = step(params, opt, batch(i), i)
            losses.append(float(m["loss"]))

    # phase 2: pod axis lost -> shrink, reshard from checkpoint, resume
    small = shrink_mesh(mesh, "pod")
    state, meta = ck.restore(template={{"params": params, "opt": opt}})
    pspec = api.model_pspecs(cfg, small)
    ospec = api.opt_pspecs(cfg, small)
    with small:
        p2 = reshard(state["params"], small, pspec)
        o2 = reshard(state["opt"], small, ospec)
        step2 = jax.jit(api.make_train_step(cfg, tcfg, small))
        p2, o2, m2 = step2(p2, o2, batch(2), 2)   # replay step 2
    print(json.dumps({{
        "replay_loss": float(m2["loss"]),
        "orig_loss": losses[2],
        "finite": bool(np.isfinite(float(m2["loss"]))),
        "new_mesh": list(small.devices.shape),
    }}))
    """))
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["finite"]
    assert r["new_mesh"] == [2, 2]
    # same global batch + restored state -> identical replayed loss
    assert abs(r["replay_loss"] - r["orig_loss"]) < 1e-4, r

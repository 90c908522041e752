"""Validation of the trip-count-aware HLO analyzer against a program with
hand-computable FLOPs/collectives (run on 8 forced host devices in a
subprocess so the main process keeps one device)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def test_analyzer_counts_loops_and_collectives():
    prog = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as PS
    from repro.launch.hlo_analysis import analyze

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    N_ITERS, B, D, F = 4, 8, 64, 128

    def f(w1, w2, x):
        def body(x, ws):
            a, b = ws
            return jnp.tanh(x @ a) @ b, None
        y, _ = jax.lax.scan(body, x, (w1, w2))
        return jax.nn.logsumexp(y)

    args = (jax.ShapeDtypeStruct((N_ITERS, D, F), jnp.float32),
            jax.ShapeDtypeStruct((N_ITERS, F, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32))
    sh = (NamedSharding(mesh, PS(None, None, "model")),
          NamedSharding(mesh, PS(None, "model", None)),
          NamedSharding(mesh, PS("data", None)))
    with mesh:
        txt = jax.jit(f, in_shardings=sh).lower(*args).compile().as_text()
    a = analyze(txt)
    # per device: dot1 [B/2, D] @ [D, F/4] = 2*B/2*F/4*D; dot2 partial
    # [B/2, F/4] @ [F/4, D] = 2*B/2*D*F/4; x N_ITERS
    want = N_ITERS * (2 * (B // 2) * (F // 4) * D
                      + 2 * (B // 2) * D * (F // 4))
    print(json.dumps({
        "flops": a["flops"], "want": want,
        "trips": [w["trips"] for w in a["while_loops"]],
        "ar_count": a["collectives"]["all-reduce"]["count"],
        "ar_bytes": a["collectives"]["all-reduce"]["bytes"],
    }))
    """)
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=300,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # forced host devices only exist on the CPU
                              # backend; without this the subprocess stalls
                              # for minutes probing for a TPU
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["flops"] == r["want"], r
    assert 4 in r["trips"], r
    # dot2's contraction is sharded -> one all-reduce of [B/2, D] f32 per
    # loop iteration (+ scalar logsumexp reductions)
    assert r["ar_count"] >= 4, r
    assert r["ar_bytes"] >= 4 * (8 // 2) * 64 * 4, r

"""Pipeline parallelism: GPipe schedule over a mesh axis vs the sequential
reference, forward and backward, on 4 fake devices (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 12) - 3 / 15) < 1e-9


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipeline_apply
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pipe",))
    S, M, MB, D = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (S, D, D)) * 0.3      # one layer per stage
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

    def block(w_s, xb):
        return jnp.tanh(xb @ w_s)

    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s])

    from jax.sharding import NamedSharding, PartitionSpec as P
    w_sh = jax.device_put(w, NamedSharding(mesh, P("pipe")))

    def piped(w_, x_):
        return pipeline_apply(block, w_, x_, mesh=mesh, axis="pipe")

    out = jax.jit(piped)(w_sh, x)
    err = float(jnp.abs(out - ref).max())

    # gradients flow through the pipeline
    def loss_p(w_, x_):
        return (pipeline_apply(block, w_, x_, mesh=mesh, axis="pipe") ** 2).sum()
    def loss_r(w_, x_):
        y = x_
        for s in range(S):
            y = jnp.tanh(y @ w_[s])
        return (y ** 2).sum()
    g_p = jax.jit(jax.grad(loss_p))(w_sh, x)
    g_r = jax.grad(loss_r)(w, x)
    gerr = float(jnp.abs(jax.device_get(g_p) - g_r).max())
    print(json.dumps({"err": err, "gerr": gerr}))
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2500:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 1e-5, res
    assert res["gerr"] < 1e-4, res

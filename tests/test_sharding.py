"""Sharding-rule tests + multi-device integration on 8 fake CPU devices
(run in a subprocess so the main test session keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec

from repro.launch.mesh import make_mesh as _mesh
from repro.sharding.partition import resolve_spec


def test_resolve_spec_divisibility_degrades():
    mesh = _mesh((1, 1), ("data", "model"))
    # model=1 divides anything; heads shard onto model
    spec = resolve_spec((2048, 4096), ("embed", "heads"), mesh)
    assert spec == PartitionSpec("data", "model")


def test_resolve_spec_no_double_claim():
    mesh = _mesh((1, 1), ("data", "model"))
    # two ff axes: only one may claim "model"
    spec = resolve_spec((512, 512), ("ff", "ff"), mesh)
    assert list(spec).count("model") == 1


def test_resolve_spec_priority_experts_first():
    mesh = _mesh((1, 1), ("data", "model"))
    spec = resolve_spec((8, 64, 128), ("experts", "embed", "ff"), mesh)
    assert spec[0] == "model" and spec[1] == "data" and spec[2] is None


SUBPROC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.configs as C
    from repro.core import msm
    from repro.launch.mesh import make_host_mesh
    from repro.models import LanguageModel
    from repro.models.base import abstract_params
    from repro.sharding.partition import batch_spec, param_shardings
    from repro.train import OptimConfig, init_opt_state, make_train_step
    from repro.train.optim import state_shardings
    from jax.sharding import NamedSharding

    mesh = make_host_mesh(data=4, model=2)
    jax.sharding.set_mesh(mesh)
    cfg = C.get("qwen3-moe-235b-a22b").smoke()
    model = LanguageModel(cfg)
    aparams = abstract_params(model.specs())
    sh = param_shardings(model.axes(), aparams, mesh)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), sh)
    opt_cfg = OptimConfig(lr=1e-3)
    opt = jax.device_put(init_opt_state(params, opt_cfg),
                         state_shardings(sh, opt_cfg, mesh))
    step = make_train_step(model, opt_cfg, microbatches=2, grad_shardings=sh)
    jitted = jax.jit(step, donate_argnums=(0, 1))
    bsh = NamedSharding(mesh, batch_spec(mesh))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size), bsh)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for i in range(4):
        params, opt, metrics = jitted(params, opt, batch, jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    # expert weights actually sharded over model axis
    we = params["layers"]["moe"]["w_gate"]
    assert len(we.sharding.device_set) == 8 or "model" in str(we.sharding.spec)
    print(json.dumps({"losses": losses}))
""")


@pytest.mark.slow
def test_multidevice_moe_train_8dev():
    """Sharded MoE training on 8 fake devices: loss finite + decreasing-ish."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SUBPROC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    losses = json.loads(out.stdout.strip().splitlines()[-1])["losses"]
    assert all(l == l and l < 30 for l in losses)  # finite, sane
    assert losses[-1] < losses[0] + 0.5


ELASTIC_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + sys.argv[1]
    import json
    import jax, jax.numpy as jnp
    import repro.configs as C
    from repro.launch.train import main
    st = main(["--arch", "granite-3-2b-smoke", "--steps", sys.argv[2],
               "--global-batch", "4", "--seq-len", "32",
               "--ckpt-dir", sys.argv[3], "--save-every", "5",
               "--log-every", "100"])
    print(json.dumps({"step": st.step}))
""")


@pytest.mark.slow
def test_elastic_resume_across_device_counts(tmp_path):
    """Train on 4 devices, checkpoint, resume the SAME run on 2 devices —
    the restore path reshards onto the smaller mesh."""
    env = dict(os.environ, PYTHONPATH="src")
    cwd = os.path.dirname(os.path.dirname(__file__))
    d = str(tmp_path / "ck")
    out1 = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT, "4", "10", d],
                          env=env, capture_output=True, text=True,
                          timeout=560, cwd=cwd)
    assert out1.returncode == 0, out1.stderr[-2000:]
    out2 = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT, "2", "15", d],
                          env=env, capture_output=True, text=True,
                          timeout=560, cwd=cwd)
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert json.loads(out2.stdout.strip().splitlines()[-1])["step"] == 15
    assert "restored step 10" in out2.stdout

"""End-to-end behaviour tests for the whole system."""
import jax
import numpy as np
import pytest

import repro.configs as C

# Training recipe per arch on one 16x16 pod (256 chips): only the two
# ~236B MoE models need the bf16-moment, no-master recipe there.
_POD_LARGE = {"qwen3-moe-235b-a22b", "deepseek-v2-236b"}


@pytest.fixture
def scoped_mesh():
    """The launcher sets a process-wide mesh; the caller's comes back after
    the test, so later tests on this worker do not compile under it."""
    from repro.launch.mesh import make_host_mesh

    with jax.sharding.set_mesh(make_host_mesh()):
        yield


def test_train_then_serve_roundtrip(tmp_path, scoped_mesh):
    """Train a tiny model a few steps, checkpoint, restore, serve tokens."""
    from repro.launch.train import main as train_main
    from repro.checkpoint.ckpt import restore
    import repro.configs as C
    from repro.models import LanguageModel
    from repro.launch.serve import ServingEngine

    d = str(tmp_path / "ck")
    st = train_main(["--arch", "tinyllama-1.1b-smoke", "--steps", "6",
                     "--global-batch", "2", "--seq-len", "32",
                     "--ckpt-dir", d, "--save-every", "3",
                     "--log-every", "100"])
    assert st.step == 6
    _, tree, extra = restore(d)
    assert extra["step"] == 6

    cfg = C.get("tinyllama-1.1b-smoke")
    model = LanguageModel(cfg)
    engine = ServingEngine(model, tree["params"], batch=2, max_len=24)
    prompts = np.ones((2, 4), np.int32)
    toks = engine.generate(prompts, steps=4)
    assert toks.shape == (2, 4)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_training_reduces_loss_learnable_data():
    """On a learnable synthetic task (memorize a fixed batch), a few dozen
    steps must reduce loss materially."""
    import repro.configs as C
    from repro.models import LanguageModel
    from repro.train import OptimConfig, init_opt_state, make_train_step

    cfg = C.get("granite-3-2b").smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = OptimConfig(lr=3e-3, warmup_steps=5, total_steps=100)
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    first = None
    for i in range(40):
        params, opt, metrics = step(params, opt, batch, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first - 1.0, (first, last)


@pytest.mark.parametrize("arch,chips,want", [
    ("tinyllama-1.1b", 1, "msm_train_large"),
    ("granite-3-2b", 4, "msm_train"),
    *[(arch, 256, "msm_train_large" if arch in _POD_LARGE else "msm_train")
      for arch in C.ARCHS],
])
def test_msm_policy_selection(arch, chips, want):
    """The software-MSM chooser composes per-domain policies (COPA SKUs),
    sizing the training recipe to the chips that hold the state."""
    from repro.core import msm

    policy = msm.recommend("train_4k", C.get(arch).n_params(), chips=chips)
    assert policy.name == want
    if want == "msm_train_large":
        assert policy.optimizer_dtype == "bfloat16"
        assert not policy.master_weights
    assert msm.recommend("long_500k", 1e9, chips=chips).kv_shard_axis == "data"
    assert msm.recommend("decode_32k", 1e9, chips=chips).remat == "none"


def test_arch_traces_feed_copa_analysis():
    """Integration: assigned-arch traces run through the paper's machinery
    and the MSM analyzer quantifies on-chip filtering per cell."""
    from repro.core import hw, msm, perfmodel
    from repro.workloads.lm import arch_trace

    t = arch_trace("yi-6b", "decode_32k")
    r = perfmodel.PerfModel(t).run(hw.GPU_N)
    assert r.time_s > 0
    an = msm.analyze(t)
    caps = sorted(an.sweep)
    assert an.sweep[caps[0]] >= an.sweep[caps[-1]] - 1e-6  # monotone


def test_dryrun_cell_runnable_matrix():
    """The 40-cell grid: skips exactly the documented long_500k cells."""
    import repro.configs as C
    from repro.configs.base import cell_is_runnable

    skipped = []
    for arch, cfg in C.ARCHS.items():
        for shape in C.SHAPES.values():
            ok, reason = cell_is_runnable(cfg, shape)
            if not ok:
                skipped.append((arch, shape.name))
    assert all(s == "long_500k" for _, s in skipped)
    assert {a for a, _ in skipped} == set(C.ARCHS) - {"mamba2-1.3b",
                                                      "zamba2-1.2b"}

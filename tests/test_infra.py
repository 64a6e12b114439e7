"""Infrastructure tests: data determinism, checkpoint atomicity/resharding,
watchdog, elastic restart, compile cache placement, HLO parsing."""
import os
import subprocess
import sys
import textwrap
import time
import uuid
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import (AsyncCheckpointer, latest_step, restore,
                                   save)
from repro.core.hloparse import parse_collectives, shape_bytes
from repro.data.pipeline import DataConfig, DataLoader, _batch_at
from repro.ft import StepWatchdog, StragglerStats


# --- data ---------------------------------------------------------------------

def test_data_deterministic_across_restarts():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=8, seed=3)
    l1 = DataLoader(cfg, start_step=0, process_index=0, process_count=1)
    first = [next(l1) for _ in range(5)]
    l1.close()
    l2 = DataLoader(cfg, start_step=3, process_index=0, process_count=1)
    resumed = [next(l2) for _ in range(2)]
    l2.close()
    for (s1, b1), (s2, b2) in zip(first[3:], resumed):
        assert s1 == s2
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


def test_data_host_sharding_disjoint():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=8, seed=0)
    b0 = _batch_at(cfg, 0, slice(0, 4))
    b1 = _batch_at(cfg, 0, slice(4, 8))
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    # labels shift tokens by one
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])


# --- checkpoint ---------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
            "b": jnp.ones((4,), jnp.bfloat16)}
    save(str(tmp_path), 7, tree, extra={"note": "hi"})
    step, out, extra = restore(str(tmp_path))
    assert step == 7 and extra["note"] == "hi"
    np.testing.assert_array_equal(out["a"]["w"], np.arange(6.0).reshape(2, 3))
    assert out["b"].dtype.name == "bfloat16"


def test_checkpoint_latest_pointer_atomic(tmp_path):
    tree = {"w": jnp.zeros(3)}
    save(str(tmp_path), 1, tree)
    save(str(tmp_path), 2, tree)
    assert latest_step(str(tmp_path)) == 2
    # partially-written garbage directory must not confuse restore
    os.makedirs(tmp_path / "step_000000099")
    assert latest_step(str(tmp_path)) == 2


def test_checkpoint_async_and_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save_async(s, {"w": jnp.full((2,), float(s))})
    ck.wait()
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and steps[-1] == "step_000000004"
    _, out, _ = restore(str(tmp_path))
    np.testing.assert_array_equal(out["w"], [4.0, 4.0])


def test_checkpoint_reshard_on_restore(tmp_path):
    """Restore onto a different sharding than saved (elastic contract)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    save(str(tmp_path), 1, {"w": jnp.arange(8.0)})
    sh = {"w": NamedSharding(mesh, PartitionSpec("data"))}
    _, out, _ = restore(str(tmp_path), shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8.0))


# --- fault tolerance ------------------------------------------------------------

def test_watchdog_detects_hang():
    wd = StepWatchdog(deadline_s=0.2, poll_s=0.05)
    with wd:
        wd.step_started()
        time.sleep(0.5)
        with pytest.raises(TimeoutError):
            wd.check()


def test_watchdog_clean_steps_no_hang():
    wd = StepWatchdog(deadline_s=0.5, poll_s=0.05)
    with wd:
        for _ in range(5):
            wd.step_started()
            time.sleep(0.02)
            wd.step_finished()
            wd.check()


def test_straggler_detection():
    st = StragglerStats(threshold=2.0, streak_to_flag=3)
    flagged = False
    for _ in range(10):
        flagged |= st.observe(1.0)
    assert not flagged
    for _ in range(3):
        flagged |= st.observe(5.0)
    assert flagged


def test_elastic_restart_resumes_from_checkpoint(tmp_path):
    """A segment that crashes mid-run restarts and completes from the last
    checkpoint, preserving step monotonicity."""
    from repro.ft import ElasticRunner, RunState

    crashes = {"n": 0}

    def mesh_factory():
        return None

    def build_state(mesh, restore_step):
        if restore_step is not None:
            _, tree, extra = restore(str(tmp_path))
            return RunState(params=tree["params"], opt_state=tree["opt"],
                            step=int(extra["step"]))
        return RunState(params={"w": jnp.zeros(2)}, opt_state={"n": 0},
                        step=0)

    def train_segment(runner, st, max_steps):
        while st.step < max_steps:
            st.params = {"w": st.params["w"] + 1.0}
            st.step += 1
            runner.maybe_save(st)
            if st.step == 5 and crashes["n"] == 0:
                crashes["n"] += 1
                runner.maybe_save(st, force=True)
                runner.ckpt.wait()
                raise RuntimeError("injected node failure")
        runner.maybe_save(st, force=True)
        runner.ckpt.wait()
        return st

    runner = ElasticRunner(str(tmp_path), mesh_factory, build_state,
                           train_segment, save_every=2)
    st = runner.run(10)
    assert st.step == 10
    assert crashes["n"] == 1
    # params reflect resumed progress (>= 10 increments minus lost tail)
    assert float(st.params["w"][0]) >= 9.0


def test_elastic_does_not_restart_a_segment_without_progress(tmp_path):
    """A segment that fails before completing its first step (a compile
    refusal, an out-of-memory) re-raises at once: a restart would only
    fail the same way."""
    from repro.ft import ElasticRunner, RunState

    builds = []

    def build_state(mesh, restore_step):
        builds.append(restore_step)
        return RunState(params={}, opt_state={}, step=0)

    def train_segment(runner, st, max_steps):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    runner = ElasticRunner(str(tmp_path), lambda: None, build_state,
                           train_segment)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        runner.run(10)
    assert builds == [None]


# --- compile cache -----------------------------------------------------------

_CACHE_PROBE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch.runtime import enable_compile_cache
    print(enable_compile_cache())
    c = float(sys.argv[1])
    jax.jit(lambda x: jnp.sin(x) * c)(jnp.ones(3)).block_until_ready()
""")


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the launchers' cache entries
    appear only there; without it, in the checkout's fixed .jax_cache."""
    from repro.launch.runtime import REPO_CACHE_DIR

    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    nonce = str(uuid.uuid4().int % 10**9)   # a program no run compiled yet

    def probe(extra_env):
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, nonce],
                             cwd=repo, env=dict(env, **extra_env),
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.splitlines()[0]

    def entries(d):
        return set(os.listdir(d)) if os.path.isdir(d) else set()

    assert probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}) == str(tmp_path)
    ours = entries(tmp_path)
    assert any(n.startswith("jit__lambda") for n in ours)
    assert not ours & entries(REPO_CACHE_DIR)
    before = entries(REPO_CACHE_DIR)
    assert probe({}) == str(REPO_CACHE_DIR)
    assert any(n.startswith("jit__lambda")
               for n in entries(REPO_CACHE_DIR) - before)


# --- HLO parsing ------------------------------------------------------------------

def test_parse_collectives_counts_bytes():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={}
  %ar.1 = f32[8,8]{1,0} all-reduce(%x), to_apply=%add
  %rs = (f32[4,4]{1,0}, f32[4,4]{1,0}) reduce-scatter(%y, %z)
  %cp-start = bf16[2,2]{1,0} collective-permute-start(%w)
  %cp-done = bf16[2,2]{1,0} collective-permute-done(%cp-start)
"""
    stats = parse_collectives(hlo)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.bytes_by_kind["all-gather"] == 16 * 1024 * 2
    assert stats.bytes_by_kind["all-reduce"] == 8 * 8 * 4
    assert stats.bytes_by_kind["reduce-scatter"] == 2 * 4 * 4 * 4
    assert stats.count_by_kind["collective-permute"] == 1  # start only


def test_shape_bytes():
    assert shape_bytes("bf16", "4,4") == 32
    assert shape_bytes("f32", "") == 4

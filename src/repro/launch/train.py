"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b-smoke \
        --steps 50 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/run1

Wires every substrate together: config -> software-MSM policy -> model ->
sharded train step -> deterministic data pipeline -> watchdog -> async
checkpointing -> elastic restart. The mesh spans every visible device
(``--mesh-model`` of them on the tensor-parallel axis, the rest on data);
the policy's optimizer recipe is sized for that many chips.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

import repro.configs as configs
from repro.checkpoint.ckpt import restore
from repro.core import msm
from repro.data.pipeline import DataConfig, DataLoader
from repro.ft import ElasticRunner, RunState, StepWatchdog
from repro.launch.mesh import make_host_mesh
from repro.launch.runtime import describe_devices, enable_compile_cache
from repro.launch.specs import optim_config_for
from repro.models import LanguageModel
from repro.models.base import abstract_params
from repro.sharding.partition import (batch_spec, param_shard_count,
                                      param_shardings)
from repro.train import init_opt_state, make_train_step
from repro.train.optim import state_shardings


def build(args, mesh, restore_step=None):
    """Model, state placed in its shardings, and the compiled train step.

    Returns (model, cfg, params, opt_state, step_fn, start_step,
    compile_seconds)."""
    cfg = configs.get(args.arch)
    policy = msm.recommend("train_4k", cfg.n_params(),
                           chips=param_shard_count(mesh))
    model = LanguageModel(cfg, impl=policy.attention_impl,
                          remat=args.remat or policy.remat)
    opt_cfg = optim_config_for(policy, lr=args.lr, warmup_steps=20,
                               total_steps=args.steps)
    shardings = param_shardings(model.axes(), abstract_params(model.specs()),
                                mesh)
    opt_shardings = state_shardings(shardings, opt_cfg, mesh,
                                    policy.grad_compression)
    jax.sharding.set_mesh(mesh)
    print(f"[train] {cfg.name} on mesh {dict(mesh.shape)}: {policy.name} "
          f"({policy.describe()})", flush=True)
    if restore_step is not None:
        _, tree, extra = restore(
            args.ckpt_dir, restore_step,
            shardings={"params": shardings, "opt": opt_shardings})
        params, opt_state = tree["params"], tree["opt"]
        start = int(extra.get("step", restore_step))
        print(f"[train] restored step {start} from {args.ckpt_dir}")
    else:
        def init(key):
            params = model.init(key)
            return params, init_opt_state(params, opt_cfg,
                                          policy.grad_compression)

        # Each device computes only its own shards: nothing is built whole.
        params, opt_state = jax.jit(
            init, out_shardings=(shardings, opt_shardings))(
                jax.random.PRNGKey(args.seed))
        start = 0
    step_fn = make_train_step(model, opt_cfg, policy.grad_compression,
                              microbatches=args.microbatches,
                              grad_shardings=shardings)
    # Outputs pinned to the input shardings: otherwise XLA may materialize
    # the optimizer math unsharded, and donation cannot alias.
    repl = NamedSharding(mesh, PartitionSpec())
    jitted = jax.jit(step_fn, donate_argnums=(0, 1), out_shardings=(
        shardings, opt_shardings,
        {"lr": repl, "grad_norm": repl, "loss": repl}))
    tokens = jax.ShapeDtypeStruct((args.global_batch, args.seq_len),
                                  jnp.int32,
                                  sharding=NamedSharding(mesh,
                                                         batch_spec(mesh)))
    batch = {"tokens": tokens, "labels": tokens, "positions": tokens}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    t0 = time.perf_counter()
    compiled = jitted.lower(params, opt_state, batch, rng).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"[train] step compiled in {compile_s:.2f}s; per device "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB arguments + "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB temporaries", flush=True)
    return model, cfg, params, opt_state, compiled, start, compile_s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory; a run resumes from the "
                         "latest checkpoint found there")
    ap.add_argument("--save-every", type=int, default=50,
                    help="checkpoint period in steps, plus one at the end "
                         "of a run; 0 never checkpoints")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    describe_devices("train")

    def mesh_factory():
        return make_host_mesh(model=args.mesh_model)

    def build_state(mesh, restore_step):
        model, cfg, params, opt, step_fn, start, compile_s = build(
            args, mesh, restore_step)
        st = RunState(params=params, opt_state=opt, step=start, mesh=mesh)
        st.model, st.cfg, st.step_fn, st.compile_s = (model, cfg, step_fn,
                                                      compile_s)
        return st

    def train_segment(runner: ElasticRunner, st: RunState, max_steps: int):
        cfg = st.cfg
        data = DataLoader(
            DataConfig(cfg.vocab_size, args.seq_len, args.global_batch,
                       seed=args.seed),
            start_step=st.step, process_index=0, process_count=1)
        bspec = NamedSharding(st.mesh, batch_spec(st.mesh))
        repl = NamedSharding(st.mesh, PartitionSpec())
        losses, step_times = [], []
        with StepWatchdog(deadline_s=300.0) as wd:
            try:
                for step, batch in data:
                    if step >= max_steps:
                        break
                    wd.check()
                    wd.step_started()
                    batch = {k: jax.device_put(v, bspec) for k, v in batch.items()}
                    rng = jax.device_put(jax.random.PRNGKey(step), repl)
                    st.params, st.opt_state, metrics = st.step_fn(
                        st.params, st.opt_state, batch, rng)
                    jax.block_until_ready((st.params, st.opt_state, metrics))
                    dt = wd.step_finished()
                    st.step = step + 1
                    runner.maybe_save(st)
                    loss = float(metrics["loss"])
                    losses.append(loss)
                    step_times.append(dt)
                    if step % args.log_every == 0:
                        print(f"step {step:5d} loss {loss:8.4f} "
                              f"gnorm {float(metrics['grad_norm']):7.3f} "
                              f"dt {dt*1e3:7.1f}ms", flush=True)
            finally:
                data.close()
        runner.maybe_save(st, force=True)
        st.final_losses, st.step_times = losses, step_times
        return st

    runner = ElasticRunner(args.ckpt_dir, mesh_factory, build_state,
                           train_segment, save_every=args.save_every)
    st = runner.run(args.steps)
    print(f"done at step {st.step}; final loss "
          f"{np.mean(st.final_losses[-10:]):.4f}")
    return st


if __name__ == "__main__":
    main()

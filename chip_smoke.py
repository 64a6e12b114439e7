"""Smoke run of the device path on TPU v5e chips.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips: sharded training only

Drives the system through the entry points a user calls, at published
widths, with random weights made from a seed:

* one chip: ``repro.launch.serve.main`` answers 8 requests on granite-3-2b
  (all 40 layers), then ``repro.launch.train.main`` takes 4 steps of
  tinyllama-1.1b at 8 x 2048 tokens;
* ``--chips 4``: ``repro.launch.train.main`` takes 3 steps of granite-3-2b
  on a data=2 x model=2 mesh. Its step-0 loss must match ``model.loss`` of
  the same initial parameters and batch on one chip, and no chip may hold
  half of the parameters and optimizer state.

Every phase checks what comes out and raises on failure. The last line of
standard output is ``{"ok": true, "device": {...}}`` only when all passed.
The script refuses to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GIB = 2 ** 30
HBM_LIMIT = 15.75 * GIB      # what the v5e lets one process allocate
SEED = 0                     # train.main's default --seed


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def memory(device) -> dict:
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory stats")
    return stats


def preflight(chips: int) -> dict:
    from repro.launch.runtime import describe_devices, enable_compile_cache

    cache = enable_compile_cache()
    dev = describe_devices("smoke")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX runs on {dev['platform']}")
    if dev["count"] != chips:
        raise SystemExit(f"chip_smoke: wants {chips} chip(s), JAX sees "
                         f"{dev['count']}")
    print(f"[smoke] compile cache: {cache}", flush=True)
    return dev


def serve_phase(arch="granite-3-2b", batch=8, prompt_len=128, gen=32,
                max_len=512):
    import jax

    import repro.configs as configs
    from repro.launch import serve

    t0 = time.perf_counter()
    toks = serve.main(["--arch", arch, "--batch", str(batch),
                       "--prompt-len", str(prompt_len), "--gen", str(gen),
                       "--max-len", str(max_len)])
    vocab = configs.get(arch).vocab_size
    check(toks.shape == (batch, gen), f"served tokens shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < vocab)).all()),
          f"served tokens outside the vocabulary [0, {vocab})")
    peak = memory(jax.devices()[0])["peak_bytes_in_use"]
    check(peak < HBM_LIMIT, f"serve peak {peak / GIB:.2f} GiB")
    print(f"[smoke] serve ok: {batch} requests x {gen} tokens of {arch}; "
          f"peak {peak / GIB:.3f} GiB; phase {time.perf_counter() - t0:.1f}s",
          flush=True)


def _train(arch, global_batch, seq_len, steps, *extra):
    from repro.launch import train

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        st = train.main(["--arch", arch, "--global-batch", str(global_batch),
                         "--seq-len", str(seq_len), "--steps", str(steps),
                         "--seed", str(SEED), "--ckpt-dir", ckpt,
                         "--save-every", "0", "--log-every", "1", *extra])
    check(st.step == steps and len(st.final_losses) == steps,
          f"trainer stopped at step {st.step}")
    check(all(math.isfinite(x) for x in st.final_losses),
          f"non-finite loss in {st.final_losses}")
    # The compiler's per-device count, temporaries included: on the v5e the
    # memory_stats() peak has read close to the step's arguments alone.
    mem = st.step_fn.memory_analysis()
    program = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    check(program < HBM_LIMIT, f"train step needs {program / GIB:.2f} GiB")
    print(f"[smoke] {arch}: compile {st.compile_s:.2f}s; step program "
          f"{program / GIB:.3f} GiB per device; step times (s, outputs "
          f"ready) {st.step_times}; losses {st.final_losses}", flush=True)
    return st


def train_phase(arch="tinyllama-1.1b", global_batch=8, seq_len=2048,
                steps=4):
    import jax

    t0 = time.perf_counter()
    _train(arch, global_batch, seq_len, steps)
    peak = memory(jax.devices()[0])["peak_bytes_in_use"]
    check(peak < HBM_LIMIT, f"train peak {peak / GIB:.2f} GiB")
    print(f"[smoke] train ok: {steps} steps of {arch} at {global_batch} x "
          f"{seq_len}; process peak {peak / GIB:.3f} GiB; phase "
          f"{time.perf_counter() - t0:.1f}s", flush=True)


def one_chip_loss(arch, global_batch, seq_len) -> float:
    """``model.loss`` of the trainer's initial parameters on its step-0
    batch, computed on device 0 alone."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import repro.configs as configs
    from repro.core import msm
    from repro.data.pipeline import DataConfig, DataLoader
    from repro.models import LanguageModel

    cfg = configs.get(arch)
    # The trainer's policy (its mesh spans every device) picks the attention
    # implementation; remat does not change the forward loss.
    policy = msm.recommend("train_4k", cfg.n_params(),
                           chips=len(jax.devices()))
    model = LanguageModel(cfg, impl=policy.attention_impl)
    dev0 = SingleDeviceSharding(jax.devices()[0])
    params = jax.jit(model.init, out_shardings=dev0)(jax.random.PRNGKey(SEED))
    data = DataLoader(DataConfig(cfg.vocab_size, seq_len, global_batch,
                                 seed=SEED),
                      start_step=0, process_index=0, process_count=1)
    try:
        step, batch = next(data)
    finally:
        data.close()
    check(step == 0, f"loader started at step {step}")
    batch = jax.device_put(batch, dev0)
    loss = float(jax.jit(model.loss)(params, batch))
    check(math.isfinite(loss), f"one-chip loss {loss}")
    return loss


def sharded_train_phase(arch="granite-3-2b", global_batch=8, seq_len=2048,
                        steps=3, mesh_model=2):
    import jax
    import numpy as np

    t0 = time.perf_counter()
    ref = one_chip_loss(arch, global_batch, seq_len)
    print(f"[smoke] one-chip step-0 loss of {arch}: {ref:.6f}", flush=True)
    st = _train(arch, global_batch, seq_len, steps,
                "--mesh-model", str(mesh_model))
    rel = abs(st.final_losses[0] - ref) / abs(ref)
    print(f"[smoke] sharded step-0 loss {st.final_losses[0]:.6f} vs one-chip "
          f"{ref:.6f}: relative difference {rel:.2e}", flush=True)
    check(rel < 1e-2, f"sharded step-0 loss off by {rel:.2e} relative")

    leaves = jax.tree.leaves((st.params, st.opt_state))
    total = sum(leaf.nbytes for leaf in leaves)
    held = {d: 0 for d in jax.devices()}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d, n in held.items():
        stats = memory(d)
        print(f"[smoke] {d}: parameters + optimizer state {n / GIB:.3f} GiB "
              f"of {total / GIB:.3f} GiB ({n / total:.1%}); in use "
              f"{stats['bytes_in_use'] / GIB:.3f} GiB, peak "
              f"{stats['peak_bytes_in_use'] / GIB:.3f} GiB", flush=True)
        check(n < 0.5 * total, f"{d} holds {n / total:.1%} of the state")
        check(stats["peak_bytes_in_use"] < HBM_LIMIT, f"{d} peak")
    print(f"[smoke] sharded train ok: {steps} steps of {arch} on "
          f"{len(held)} chips; largest share "
          f"{max(held.values()) / total:.1%}; phase "
          f"{time.perf_counter() - t0:.1f}s; mean step "
          f"{np.mean(st.step_times[1:]):.4f}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training phase")
    args = ap.parse_args(argv)
    dev = preflight(args.chips)
    if args.chips == 4:
        sharded_train_phase()
    else:
        serve_phase()
        train_phase()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

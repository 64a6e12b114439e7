import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
``jax.jit(step).lower(**abstract_inputs).compile()`` must succeed on the
single-pod 16x16 mesh AND the 2x16x16 multi-pod mesh for every cell, and the
per-device memory/cost analyses feed EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]

Results are cached per cell in the output JSON (incremental; safe to re-run).
"""

import argparse
import json
import time
import traceback

import jax

import repro.configs as configs
from repro.configs.base import cell_is_runnable
from repro.core.hloparse import parse_collectives
from repro.core.hlo_cost import analyze_hlo_cost
from repro.core.roofline import model_flops_lm
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, optim_config_for
from repro.sharding.partition import param_shard_count
from repro.core import msm
from repro.train import make_train_step
from repro.serve.step import make_decode_step, make_prefill_step


def _clamp_microbatches(policy_mb: int, gb: int, mesh) -> int:
    """Largest mb <= policy that leaves an integer per-shard batch."""
    shards = 1
    for a, n in zip(mesh.axis_names, mesh.devices.shape):
        if a in ("pod", "data"):
            shards *= n
    per_shard = max(gb // shards, 1)
    mb = min(policy_mb, per_shard)
    while per_shard % mb:
        mb -= 1
    return max(mb, 1)


def build_step(kind: str, model, policy, abstract_args=None, mesh=None,
               global_batch=None):
    if kind == "train":
        opt_cfg = optim_config_for(policy)
        mb = policy.microbatches
        if mesh is not None and global_batch:
            mb = _clamp_microbatches(policy.microbatches, global_batch, mesh)
        grad_sh = batch_sh = None
        if abstract_args is not None:
            import jax as _jax
            from jax.sharding import NamedSharding, PartitionSpec
            aparams, _, abatch, _ = abstract_args
            grad_sh = _jax.tree.map(lambda a: a.sharding, aparams)
            def mb_shard(a):
                spec = a.sharding.spec
                return NamedSharding(a.sharding.mesh,
                                     PartitionSpec(None, *spec))
            batch_sh = _jax.tree.map(mb_shard, abatch)
        step = make_train_step(model, opt_cfg, policy.grad_compression,
                               microbatches=mb,
                               grad_shardings=grad_sh,
                               batch_shardings=batch_sh)

        def train(params, opt_state, batch, rng):
            return step(params, opt_state, batch, rng)

        return train, dict(donate_argnums=(0, 1))
    if kind == "prefill":
        prefill = make_prefill_step(model)
        return prefill, {}
    decode = make_decode_step(model)

    def dec(params, cache, tokens, pos, rng):
        return decode(params, cache, tokens, pos, rng)

    return dec, dict(donate_argnums=(1,))


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    ok, reason = cell_is_runnable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        return dict(base, status="skipped", reason=reason)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = msm.recommend(shape.name, cfg.n_params(),
                           chips=param_shard_count(mesh))
    kind, model, abstract_args, out_sh = input_specs(arch, shape_name, mesh,
                                                     policy)
    step_fn, jit_kw = build_step(kind, model, policy, abstract_args,
                                 mesh=mesh, global_batch=shape.global_batch)

    jax.sharding.set_mesh(mesh)
    lowered = jax.jit(step_fn, out_shardings=out_sh,
                      **jit_kw).lower(*abstract_args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll = parse_collectives(hlo_text)
    # trip-count-expanded accounting (XLA counts while bodies once)
    adj = analyze_hlo_cost(hlo_text)

    chips = mesh.devices.size
    tokens = shape.global_batch * (shape.seq_len if kind != "decode" else 1)
    n_active = cfg.n_active_params()
    result = dict(
        base,
        status="ok",
        step=kind,
        policy=policy.name,
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops_per_device=float(cost.get("flops", 0.0)) if cost else 0.0,
        bytes_per_device=float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        collective_bytes_per_device=coll.total_bytes,
        collectives=coll.as_dict(),
        flops_adjusted=adj.dot_flops,
        bytes_adjusted=adj.bytes_accessed,
        collective_adjusted=adj.collective_bytes,
        collective_adjusted_by_kind={k: float(v) for k, v in
                                     adj.collective_by_kind.items()},
        model_flops=model_flops_lm(n_active, tokens, training=(kind == "train")),
    )
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            result[attr] = int(getattr(mem, attr, 0) or 0)
        result["peak_memory_per_device"] = (
            result.get("temp_size_in_bytes", 0)
            + result.get("argument_size_in_bytes", 0)
            - result.get("alias_size_in_bytes", 0)
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    cells = []
    archs = list(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(configs.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    for arch, shape, mp in cells:
        key = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
        if key in results and results[key].get("status") in ("ok", "skipped") \
                and not args.force:
            print(f"[cached] {key}: {results[key]['status']}")
            continue
        print(f"[run] {key} ...", flush=True)
        try:
            res = run_cell(arch, shape, mp)
        except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results[key] = res
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        if res["status"] == "ok":
            print(f"  ok: compile={res['compile_s']}s "
                  f"flops/dev={res['flops_per_device']:.3e} "
                  f"bytes/dev={res['bytes_per_device']:.3e} "
                  f"coll/dev={res['collective_bytes_per_device']:.3e} "
                  f"peakmem/dev={res.get('peak_memory_per_device', 0)/2**30:.2f}GiB",
                  flush=True)
        else:
            print(f"  {res['status']}: {res.get('reason') or res.get('error')}",
                  flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\nSummary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())

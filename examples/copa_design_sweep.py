"""The paper's design-space exploration through the public API: one
SweepEngine grid over the COPA configurations (Table V) x the MLPerf-proxy
suites AND the assigned LM architectures, printing the Fig-11-style table,
the Fig-12-style scale-out projection (instances x ICI fabric), the serving
latency/throughput grid per MSM, the software-MSM recommendation per LM
cell, and the one-call FULL-REGISTRY sweep (every scenario namespace x
Table V through a single suite-batched pass).

    PYTHONPATH=src python examples/copa_design_sweep.py
"""
import sys
import time

sys.path.insert(0, "src")

import repro.configs as configs
from repro.core import copa, msm
from repro.core.hw import MB
from repro.core.sweep import SweepEngine, geomean
from repro.workloads import registry

SUITES = ("mlperf.train.large", "mlperf.train.small",
          "mlperf.infer.large", "mlperf.infer.small")


def paper_suite_table():
    print("=== COPA design space (Table V / Fig 11) — MLPerf proxies ===")
    names = [n for s in SUITES for n in registry.suite(s)]
    grid = SweepEngine(names, configs=copa.TABLE_V).run()
    header = f"{'config':12s} {'train-lb':>9s} {'train-sb':>9s} {'infer-lb':>9s} {'infer-sb':>9s}"
    print(header)
    for cfg in copa.TABLE_V:
        cells = []
        for s in SUITES:
            traces = [registry.scenario(n).name for n in registry.suite(s)]
            cells.append(f"{grid.geomean_speedup(cfg.name, traces):9.3f}")
        print(f"{cfg.name:12s} " + " ".join(cells))


def scale_out_table():
    """Fig-12-style projection: fixed-global-batch DP training across 1/2/4
    GPU instances, ideal fabric vs a 600 GB/s ring all-reduce."""
    print("\n=== Scale-out projection (Fig 12): instances x ICI fabric ===")
    works = registry.scaleout_names("scaleout.mlperf.train.")
    names = [registry.scaleout(w).name for w in works]
    for label, ici in (("ideal fabric", float("inf")),
                       ("600GB/s ring", 600e9)):
        grid = SweepEngine(works, configs=[copa.GPU_N_BASE, copa.HBML_L3],
                           gpu_counts=(1, 2, 4), ici_bandwidth=ici).run()
        copa1 = grid.geomean_speedup("HBML+L3", names)
        n2 = geomean(grid.speedups("GPU-N", names, n_gpus=2))
        n4 = geomean(grid.speedups("GPU-N", names, n_gpus=4))
        eff2 = geomean(grid.result(t, "GPU-N", 2).scaling_efficiency
                       for t in names)
        reached = [n for n in
                   grid.instances_to_match("GPU-N", "HBML+L3", names).values()
                   if n is not None]
        inst = sum(reached) / len(reached) if reached else float("nan")
        print(f"{label:14s} HBML+L3@1={copa1:5.3f}  GPU-Nx2={n2:5.3f} "
              f"(eff {eff2:4.2f})  GPU-Nx4={n4:5.3f}  "
              f"GPU-N instances/COPA={inst:.2f} "
              f"({len(reached)}/{len(names)} matchable)")


def serve_grid_table():
    """Serving latency/throughput grid: batched decode per MSM config."""
    print("\n=== Serving grid: batch x MSM (per-request latency, ms) ===")
    configs_ = [copa.GPU_N_BASE, copa.HBM_L3, copa.HBML_L3]
    header = f"{'batch':>6s}" + "".join(f" {c.name:>10s}" for c in configs_)
    print(header)
    for b in registry.SERVE_BATCHES:
        names = registry.suite(f"serve.b{b}")
        grid = SweepEngine(names, configs=configs_).run()
        cells = []
        for c in configs_:
            lat = geomean(grid.result(registry.scenario(n).name, c.name).time_s
                          for n in names) * 1e3
            cells.append(f" {lat:10.3f}")
        print(f"{b:6d}" + "".join(cells))


def arch_msm_table():
    print("\n=== Assigned architectures: COPA analysis + software-MSM ===")
    cells = [(arch, shape) for arch in configs.ARCHS
             for shape in ("train_4k", "decode_32k")]
    # One suite-batched Fig-4 pass over all 20 cells (msm.analyze_suite),
    # instead of one trace walk per cell.
    traces = [registry.scenario(f"lm.{a}.{s}") for a, s in cells]
    for (arch, shape), an in zip(cells, msm.analyze_suite(traces)):
        red = min(an.baseline_traffic / max(an.sweep[960 * MB], 1e-9), 999)
        policy = msm.recommend(shape, configs.get(arch).n_params(),
                               chips=256)   # one 16x16 production pod
        print(f"{arch:24s} {shape:10s} 960MB-filter={red:6.1f}x  "
              f"msm={policy.name:16s} ({policy.describe()})")


def full_registry_sweep():
    """Every registered scenario x Table V in ONE suite-batched pass —
    the design-space product the per-trace loop made impractical."""
    print("\n=== Full-registry sweep: one StreamBatch pass ===")
    names = registry.scenarios()
    t0 = time.time()
    grid = SweepEngine(names, configs=copa.TABLE_V).run()
    dt = time.time() - t0
    print(f"{len(names)} scenarios x {len(copa.TABLE_V)} configs -> "
          f"{len(grid.rows)} rows in {dt * 1e3:.0f}ms")
    by_ns = {"mlperf.train": "mlperf.train.", "mlperf.infer": "mlperf.infer.",
             "serve": "serve.", "lm": "lm.", "hpc": "hpc."}
    import math

    for label, prefix in by_ns.items():
        traces = [registry.scenario(n).name for n in names
                  if n.startswith(prefix)]
        sp = [s for s in grid.speedups("HBML+L3", traces)
              if math.isfinite(s) and s > 0]
        geo = geomean(sp)
        note = "" if len(sp) == len(traces) else \
            f" ({len(traces) - len(sp)} degenerate cells skipped)"
        print(f"  {label:14s} {len(traces):4d} scenarios  "
              f"HBML+L3 geomean speedup {geo:.3f}{note}")


if __name__ == "__main__":
    paper_suite_table()
    scale_out_table()
    serve_grid_table()
    arch_msm_table()
    full_registry_sweep()

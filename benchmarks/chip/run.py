"""On-chip benchmark: run one cell of BENCHMARK.json on the chips here.

    python3 benchmarks/chip/run.py --workload granite-3-2b.chat \
        --seed 12345 --seconds 10 --trace 0

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name, so that a new cell is files and entries only:

* ``BENCHMARK.json`` names the cell's configuration, traffic and chips;
* ``configs/<config>.json`` holds the configuration as run; the program's
  ``ModelConfig`` is built from its ``model_config``;
* ``traffic/<traffic>.json`` holds the mix, and names the adapter
  ``entries/<entry>.py`` that drives the program entry with it;
* ``limits/<workload>.json`` holds the limit of each number compared;
* ``metrics/<metric>.py`` reads one per-layer metric (``read(run, cell)``,
  None where it finds nothing to read), in the cells its entry lists under
  ``workloads``.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, a device
trace of the wave that the traffic file names, and the breakdown. The run refuses to
start where ``REPRO_OPTS`` is set, and exits non-zero with no result where
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Refused(Exception):
    """The cell cannot run here; no result is printed."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    peaks: dict = field(default_factory=dict)
    peak: dict = field(default_factory=dict)
    tracer: object = None
    t_start: float = T_START

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def validate(bench: dict) -> None:
    """Refuse names and units that the benchmark's readers would refuse."""
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        if not isinstance(n, str) or not NAME.match(n):
            raise Refused(f"name {n!r} is not 1-64 of [A-Za-z0-9_.-]")
    for m in metrics:
        if not UNIT.match(m["unit"]):
            raise Refused(f"unit {m['unit']!r} of {m['name']} is not 1-16 of "
                          f"[A-Za-z0-9_/%.-]")
    for kind in ("configs", "workloads"):
        seen = [x["name"] for x in bench[kind]]
        if len(seen) != len(set(seen)):
            raise Refused(f"two {kind} share a name")
    if len({m["name"] for m in metrics}) != len(metrics):
        raise Refused("two metrics share a name")
    for m in bench["per_layer"]:
        if not m.get("workloads"):
            raise Refused(f"per-layer metric {m['name']} lists no workloads")


def load_cell(root: Path, workload: str, seed: int, seconds: float,
              trace: bool) -> tuple[dict, Cell]:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    validate(bench)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    peaks = json.loads((HERE / "peaks.json").read_text())
    return bench, Cell(workload, config, traffic, limits, int(w["chips"]),
                       seed, seconds, trace, peaks)


def load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    """The cell's end-to-end metrics (those that list the cell or list no
    cells), or its per-layer metrics (those that list the cell)."""
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]]
    return [m for m in bench["per_layer"] if workload in m["workloads"]]


def prepare_jax(root: Path, cell: Cell):
    """Compile cache in the checkout; the cell's chips, or Refused."""
    if os.environ.get("REPRO_OPTS"):
        raise Refused("REPRO_OPTS is set; it changes the program's layout "
                      "and numerics at import, and no cell runs under it")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < cell.chips:
        raise Refused(f"the cell asks for {cell.chips} chips; JAX finds "
                      f"{len(devices)}")
    if devices[0].device_kind not in cell.peaks:
        raise Refused(f"no peaks for device kind {devices[0].device_kind!r}")
    cell.peak = cell.peaks[devices[0].device_kind]
    return devices[:cell.chips]


def import_program(root: Path) -> None:
    sys.path.insert(0, str(root / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}") from e


def run_cell(bench: dict, cell: Cell, devices, root: Path) -> dict:
    """Drive the cell's adapter, then reduce its records to the result."""
    import endtoend
    import devtrace as tr

    if cell.trace:
        cell.tracer = tr.Tracer(str(root / ".bench_trace" / cell.name))
    entry = load_module(HERE / "entries" / f"{cell.traffic['entry']}.py", "entry")
    run = entry.run(cell)

    checks = {k: {"value": v, "limit": cell.limits[k]["limit"]}
              for k, v in run["numbers"].items() if k in cell.limits}
    missing = set(cell.limits) - set(checks)
    correct = (not missing and run["failed"] == 0 and run["attempted"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": run["attempted"],
           "failed": run["failed"], "metrics": {}, "device": device}
    if not cell.trace:
        for m in metrics_of(bench, cell.name, "end_to_end"):
            v = getattr(endtoend, m["name"])(run)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        t = run["trace"]
        device["busy_s"], device["window_s"] = tr.busy_s(t), tr.window_s(t)
        for m in metrics_of(bench, cell.name, "per_layer"):
            v = load_module(HERE / "metrics" / f"{m['name']}.py", "metric").read(run, cell)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
    for k in sorted(missing):
        checks[k] = {"value": None, "limit": cell.limits[k]["limit"]}
    out["checks"] = checks
    return out


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell = load_cell(root, args.workload, args.seed, args.seconds,
                                bool(args.trace))
        import_program(root)
        devices = prepare_jax(root, cell)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    out = run_cell(bench, cell, devices, root)
    for k, c in out["checks"].items():
        print(f"[bench] check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(f"[bench] correct = {out['correct']}; failed {out['failed']} of "
          f"{out['attempted']} requests", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

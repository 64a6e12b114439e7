"""Device trace: capture a window with the JAX profiler and reduce it.

``Tracer.start`` opens a profile where the traffic file's ``trace`` says
(at the start of one wave, or at its first token); ``Tracer.stop`` ends it
when that wave returns, or a timer ``seconds`` after the start, whichever
comes first, while the program runs on. ``reduce_xplane`` keeps what the
metrics read, as plain lists that a test can store:

* ``devices``: per TPU plane, the "XLA Modules" line (one event per program
  run, named after the jitted function) and the "XLA Ops" line (one event
  per operation), each ``[name, start_ns, duration_ns]``;
* ``window_ns``: the traced window, from the first device operation to
  the end of the last (the device's events begin some 45 ms after the
  profile starts, while the chip is already busy);
* ``host``: what the host was doing, ``[span, start_ns, duration_ns]`` on
  the same clock. The adapter fills it from its own clock, counted from
  the moment it starts the profile: a span that began before the profile
  or ended after it is not in the profile itself.

The functions below turn that into busy and idle time, program time,
collective time and the breakdown of the result line.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import threading

SPANS = ("engine.prefill", "engine.generate")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# An op event's name is its HLO instruction; a loop or call contains the
# events of the ops it runs, so it is left out.
CONTAINER = re.compile(r"^%?[\w.-]+ = .*\b(while|conditional|call)\(")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|"
                        r"all-to-all|send|recv", re.I)


class Tracer:
    """Profiles into ``directory`` from ``start`` to ``stop``."""

    def __init__(self, directory: str):
        self.directory = directory
        self._lock = threading.Lock()
        self._timer = None
        self._running = False

    def start(self, seconds: float) -> None:
        """Open the profile; a timer stops it ``seconds`` later."""
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._running = True
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.start()

    def stop(self) -> None:
        """End the profile, if it still runs; safe from any thread."""
        import jax

        with self._lock:
            if self._running:
                self._running = False
                jax.profiler.stop_trace()

    def join(self) -> None:
        self._timer.cancel()
        self.stop()
        self._timer.join()

    def result(self) -> dict:
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        try:
            return reduce_xplane(paths[0])
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "window_ns": None}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                ln.name: [[short_name(e.name), e.start_ns, e.duration_ns]
                          for e in ln.events if not CONTAINER.search(e.name)]
                for ln in plane.lines if ln.name in ("XLA Modules", "XLA Ops")}
    evs = [ev for dev in out["devices"].values() for line in dev.values()
           for ev in line]
    if not evs:
        raise RuntimeError("the trace holds no device operation")
    out["window_ns"] = [min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs)]
    return out


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals, lo, hi) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _ops(dev: dict) -> list:
    return dev.get("XLA Ops") or dev.get("XLA Modules") or []


def window_s(trace: dict) -> float:
    lo, hi = trace["window_ns"]
    return (hi - lo) / 1e9


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    lo, hi = trace["window_ns"]
    per = [sum(e - s for s, e in _union(_ops(d), lo, hi))
           for d in trace["devices"].values()]
    return sum(per) / len(per) / 1e9


def program_runs(trace: dict, needle: str) -> list[list]:
    """Runs of the programs whose module name contains ``needle``, on the
    first chip, in time order: ``[name, start_ns, duration_ns]``."""
    first = trace["devices"][sorted(trace["devices"])[0]]
    return sorted((ev for ev in first.get("XLA Modules", []) if needle in ev[0]),
                  key=lambda ev: ev[1])


def collective_s(trace: dict) -> float:
    """Device seconds of collective operations, averaged over the chips."""
    per = [sum(d for n, _, d in _ops(dev) if COLLECTIVE.search(n))
           for dev in trace["devices"].values()]
    return sum(per) / len(per) / 1e9


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The operations that took most device time: [[name, seconds], ...],
    averaged over the chips."""
    tot: dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, _, d in _ops(dev):
            tot[name] = tot.get(name, 0.0) + d
    k = len(trace["devices"])
    return [[name, s / k / 1e9] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list[list]:
    """The longest gaps in the first chip's operations, each named by the
    innermost benchmark span that covers its middle ("harness" if none)."""
    lo, hi = trace["window_ns"]
    first = trace["devices"][sorted(trace["devices"])[0]]
    busy = _union(_ops(first), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [h for h in trace["host"] if h[1] <= mid <= h[1] + h[2]]
        name = min(covering, key=lambda h: h[2])[0] if covering else "harness"
        out.append([name, (e - s) / 1e9])
    return out

"""Elastic run control: checkpoint/restart across mesh-shape changes.

``ElasticRunner`` owns the restart loop around a train function:

    runner = ElasticRunner(ckpt_dir, build_state, train_segment)
    runner.run(max_steps)

* ``build_state(mesh, restore_step)`` constructs (params, opt_state, step)
  — restoring and RESHARDING from the latest checkpoint when one exists
  (the checkpoint layer stores arrays by name, so any mesh shape whose
  shardings the caller provides will do: scale 16 hosts -> 12 hosts and the
  same checkpoint restores onto the smaller mesh).
* ``train_segment(state, steps)`` runs until it returns (completed) or
  raises (hang/preemption) — the runner saves, rebuilds the mesh with
  whatever devices are now healthy, and resumes. The segment advances
  ``state.step`` as it completes steps; one that raises before completing
  any step since its start (a compile refusal, an out-of-memory, a bad
  argument) would only fail the same way again, so it is not restarted.

On real fleets mesh health comes from the cluster scheduler; here
``mesh_factory`` abstracts it (tests inject shrinking device sets).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

from repro.checkpoint.ckpt import AsyncCheckpointer, latest_step


@dataclass
class RunState:
    params: object
    opt_state: object
    step: int
    mesh: object = None
    restarts: int = 0


@dataclass
class QueueDepthAutoscaler:
    """Queue-depth-driven fleet sizing for the serving simulator.

    The serving-side face of elastic run control: where :class:`ElasticRunner`
    resizes a training mesh across restarts, this policy resizes a serving
    fleet (``repro.serve.fleet.FleetSim``) at a fixed cadence from what a
    real autoscaler can observe — queue depth and running batch occupancy.

    Thresholds are in units of FULL BATCHES per instance — a loaded-but-
    stable instance naturally runs with a batch or two waiting, so absolute
    request counts would flap at the correct size:

    * scale UP by one when more than ``high_batches`` full batches per
      instance are waiting AND the backlog is not already draining (an
      undersized fleet has an ever-growing queue; a recovering one should
      not keep adding instances);
    * scale DOWN by one when the queue is near-empty (< ``low_batches``)
      and the running work would fit ``n - 1`` instances at ``down_util``
      batch utilization.

    Under stationary load this converges to the smallest stable fleet —
    within one instance of ``instances_to_meet_slo`` for any SLO loose
    enough to be queue-stability-bound (asserted in tests).
    """

    high_batches: float = 2.0
    low_batches: float = 0.25
    down_util: float = 0.7
    min_instances: int = 1
    max_instances: int = 64
    _last_queued: float = field(default=-1.0, init=False, repr=False)

    def decide(self, n_active: int, queued: int, running: int,
               max_batch: int) -> int:
        # Both fleet engines (the per-instance oracle and the vectorized
        # core in ``repro.serve.fleetbatch``) call this at autoscale ticks;
        # coerce observations so numpy scalars from the batched engine and
        # plain ints from the oracle drive bit-identical decisions.
        n_active, queued, running = int(n_active), int(queued), int(running)
        capacity = max(n_active, 1) * max_batch
        growing = self._last_queued < 0 or queued >= self._last_queued
        self._last_queued = float(queued)
        if queued > self.high_batches * capacity and growing:
            return min(n_active + 1, self.max_instances)
        if (queued < self.low_batches * capacity
                and n_active > self.min_instances
                and running <= (n_active - 1) * max_batch * self.down_util):
            return max(n_active - 1, self.min_instances)
        return n_active


class ElasticRunner:
    def __init__(self, ckpt_dir: str, mesh_factory: Callable[[], object],
                 build_state: Callable, train_segment: Callable,
                 max_restarts: int = 10, save_every: int = 100):
        self.ckpt_dir = ckpt_dir
        self.mesh_factory = mesh_factory
        self.build_state = build_state
        self.train_segment = train_segment
        self.max_restarts = max_restarts
        self.save_every = save_every
        self.ckpt = AsyncCheckpointer(ckpt_dir)

    def run(self, max_steps: int) -> RunState:
        restarts = 0
        while True:
            mesh = self.mesh_factory()
            start = latest_step(self.ckpt_dir)
            state = self.build_state(mesh, start)
            state.mesh = mesh
            state.restarts = restarts
            first_step = state.step
            try:
                state = self.train_segment(self, state, max_steps)
                self.ckpt.wait()
                return state
            except Exception as e:  # noqa: BLE001 — restart-able failure
                if state.step == first_step:
                    raise
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                print(f"[elastic] segment failed ({type(e).__name__}: {e}); "
                      f"restart {restarts}/{self.max_restarts}")
                time.sleep(0.1)

    def maybe_save(self, state: RunState, force: bool = False):
        """Checkpoint every ``save_every`` steps, or now with ``force``;
        ``save_every <= 0`` never checkpoints."""
        if self.save_every <= 0:
            return
        if force or (state.step > 0 and state.step % self.save_every == 0):
            self.ckpt.save_async(
                state.step,
                {"params": state.params, "opt": state.opt_state},
                extra={"step": state.step})

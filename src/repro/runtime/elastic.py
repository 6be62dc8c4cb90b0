"""Elastic checkpoint-restart training driver.

The loop every large-scale trainer runs:

    while budget:
        try:   train until failure (heartbeats checked between steps)
        except/on-failure:
               drop dead workers -> rebuild a smaller mesh from survivors
               -> re-derive shardings -> RESTORE latest checkpoint with
               reshard-on-restore -> continue

The driver is device-count-agnostic: on this container it exercises the
full logic with simulated failures (FailureInjector raises at chosen
steps and shrinks the device set), which is exactly the path a real
deployment takes when jax.distributed reports a lost host. Mesh shapes
degrade along the data axis first (model parallelism is assumed intact
within surviving nodes — a failed TP group kills its whole replica).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import CheckpointManager
from repro.runtime.fault import HeartbeatMonitor, StragglerDetector


class WorkerFailure(RuntimeError):
    def __init__(self, workers: Sequence[str]):
        super().__init__(f"workers failed: {list(workers)}")
        self.workers = list(workers)


@dataclasses.dataclass
class FailureInjector:
    """Deterministic failure schedule for tests/examples: step -> #devices
    to drop."""
    schedule: dict[int, int]

    def check(self, step: int, devices: list) -> list:
        drop = self.schedule.get(step, 0)
        if drop and len(devices) > drop:
            raise WorkerFailure([str(d.id) for d in devices[-drop:]])
        return devices


def build_mesh_from(devices: Sequence, model_parallel: int) -> Mesh:
    """Largest (data, model) mesh from the surviving devices."""
    n = len(devices)
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    dp = n // mp
    devs = np.asarray(devices[:dp * mp]).reshape(dp, mp)
    return Mesh(devs, ("data", "model"))


@dataclasses.dataclass
class ElasticTrainer:
    """Wires train_step + checkpoint manager + failure handling together.

    make_state:  (mesh) -> (params, opt_state, step_fn, shardings) — called
                 on every (re)mesh;
    ckpt:        CheckpointManager;
    save_every:  checkpoint cadence in steps.
    """
    make_state: Callable[[Mesh], tuple[Any, Any, Callable, Any]]
    ckpt: CheckpointManager
    save_every: int = 10
    model_parallel: int = 1
    heartbeat_timeout_s: float = 30.0

    def run(self, batches, num_steps: int,
            injector: FailureInjector | None = None,
            devices: Sequence | None = None) -> dict:
        devices = list(devices if devices is not None else jax.devices())
        monitor = HeartbeatMonitor(timeout_s=self.heartbeat_timeout_s)
        stragglers = StragglerDetector()
        history: list[float] = []
        restarts = 0
        step = 0

        while step < num_steps:
            mesh = build_mesh_from(devices, self.model_parallel)
            params, opt_state, step_fn, shardings = self.make_state(mesh)
            latest = None
            try:
                (params, opt_state), latest = self.ckpt.restore_latest(
                    (params, opt_state), shardings)
                step = latest
                # Steps latest..failure-1 are about to re-run; their
                # pre-failure losses would otherwise stay as duplicates
                # (history[i] is step i's loss, appended before step += 1).
                del history[latest:]
            except FileNotFoundError:
                pass
            # Monitor exactly the mesh's devices: build_mesh_from takes
            # devices[:dp*mp], and heartbeats/step-times recorded for a
            # device OUTSIDE the mesh would keep reporting it as a live
            # (or straggling) worker it no longer is.
            in_mesh = devices[:mesh.devices.size]

            try:
                while step < num_steps:
                    if injector is not None:
                        devices = injector.check(step, devices)
                    t0 = time.monotonic()
                    batch = next(batches)
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch, mesh)
                    dt = time.monotonic() - t0
                    for d in in_mesh:
                        monitor.beat(str(d.id))
                        stragglers.record(str(d.id), dt)
                    history.append(float(metrics["loss"]))
                    step += 1
                    if step % self.save_every == 0 or step == num_steps:
                        self.ckpt.save_async(step, (params, opt_state))
                self.ckpt.wait()
            except WorkerFailure as wf:
                restarts += 1
                self.ckpt.wait()
                dead = set(wf.workers)
                devices = [d for d in devices if str(d.id) not in dead]
                # Dead workers leave the monitors too: a restart must not
                # carry their stale heartbeats/step-times into the shrunk
                # mesh's failure or straggler reports.
                for w in dead:
                    monitor.remove(w)
                    stragglers.remove(w)
                if not devices:
                    raise
                continue

        return {"losses": history, "restarts": restarts,
                "final_devices": len(devices),
                "monitored": monitor.workers(),
                "stragglers": stragglers.stragglers()}

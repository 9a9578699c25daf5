"""Elastic scaling + failure handling.

On a real pod, a node failure surfaces as a collective timeout / missing
participant.  The recovery loop is: detect -> rebuild the mesh from the
surviving device set -> reshard (or restore) state onto it -> continue.
``reshard`` moves live pytrees between meshes; ``pick_mesh_shape`` chooses the
largest (data, model) grid for a device count while respecting the model-
parallel width the params were built for; ``ElasticRunner`` packages the loop
(failures injected in tests via the ``fault`` hook)."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
from jax.sharding import Mesh

from repro.runtime import checkpoint as ckpt


def pick_mesh_shape(n_devices: int, model: int = 0) -> tuple:
    """Largest (data, model) grid for n_devices.  model=0 -> widest power-of-
    two model axis <= n_devices (params sharded that way keep working)."""
    if model <= 0:
        model = 1
        while model * 2 <= min(n_devices, 16):
            model *= 2
    while n_devices % model:
        model //= 2
    return (n_devices // model, model)


def make_mesh_from(devices, model: int = 0) -> Mesh:
    shape = pick_mesh_shape(len(devices), model)
    import numpy as np
    arr = np.asarray(devices)[:shape[0] * shape[1]].reshape(shape)
    return Mesh(arr, ("data", "model"))


def reshard(tree, shardings):
    """Move a live pytree onto new shardings (cross-mesh).  Falls back to a
    host round-trip when direct transfer is not possible."""
    def move(x, s):
        try:
            return jax.device_put(x, s)
        except Exception:
            # cross-mesh transfers some backends refuse: stage through host
            import numpy as np
            return jax.device_put(np.asarray(jax.device_get(x)), s)

    return jax.tree.map(move, tree, shardings)


@dataclasses.dataclass
class ElasticRunner:
    """Run a step function under simulated-failure recovery.

    step_fn(state, batch, mesh) -> state; on NodeFailure the runner shrinks
    the mesh, reshards the live state (or restores the last checkpoint AND
    rewinds the data stream to it — deterministic per-(seed, step) data
    generation makes the replay exact), then continues.  No step is skipped.
    """

    make_shardings: Callable   # mesh -> shardings pytree for state
    ckpt_dir: Optional[str] = None
    max_recoveries: int = 8

    def run(self, state, make_batches, step_fn, mesh, *,
            fault: Optional[Callable[[int], None]] = None,
            ckpt_every: int = 0):
        """make_batches(start_step) -> iterator of batches from that step."""
        if not callable(make_batches):
            seq = list(make_batches)
            make_batches = lambda s: iter(seq[s:])  # noqa: E731
        recoveries = 0
        saver = (ckpt.AsyncCheckpointer(self.ckpt_dir)
                 if self.ckpt_dir else None)
        step = 0
        it = enumerate(make_batches(0))
        while True:
            try:
                try:
                    step, batch = next(it)
                except StopIteration:
                    break
                if fault is not None:
                    fault(step)  # may raise NodeFailure
                state = step_fn(state, batch, mesh)
                if saver and ckpt_every and step % ckpt_every == 0:
                    saver.wait()  # surface async errors promptly
                    saver.save(step, state)
            except NodeFailure as e:
                recoveries += 1
                if recoveries > self.max_recoveries:
                    raise
                mesh = make_mesh_from(e.surviving_devices)
                shardings = self.make_shardings(mesh)
                if self.ckpt_dir and \
                        ckpt.latest_step(self.ckpt_dir) is not None:
                    if saver:
                        saver.wait()
                    state, restored = ckpt.restore(self.ckpt_dir, state,
                                                   shardings=shardings)
                    resume = restored + 1  # replay everything after it
                else:
                    state = reshard(state, shardings)
                    resume = step  # live state is current; retry this step
                it = enumerate(make_batches(resume), start=resume)
        if saver:
            saver.wait()
        return state, mesh, recoveries


class NodeFailure(RuntimeError):
    """Raised (by monitoring, or injected in tests) when devices drop."""

    def __init__(self, surviving_devices):
        super().__init__(f"{len(surviving_devices)} devices survive")
        self.surviving_devices = list(surviving_devices)

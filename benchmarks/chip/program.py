"""The system under test, built the way ``launch/train.run`` builds it, and
the host loop that drives it.

``make_mesh`` -> ``train_mesh`` -> ``build_sparq`` -> ``jax.jit(train_step,
in_shardings=(state, batch), donate_argnums=(0,))``, compiled ahead of time,
with the state made on its shardings by ``jax.jit(init_fn)`` from the seed.
Each step builds its batch on the host, ``device_put``s it and calls the
compiled step, as ``run``'s loop does; the loop here enqueues step i + 1
before it waits on step i, so each step's completion time is known and the
timing adds no idle.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from reference.common import model_key


@dataclasses.dataclass
class Program:
    compiled: Any          # the AOT-compiled step
    train_step: Any        # build_sparq's step (.lowering, .n_nodes, ...)
    state: Any             # the train state, on its shardings
    bsh: Any               # batch shardings
    compile_s: float
    init: Any              # key -> a fresh state, on its shardings

    def reset(self, seed: int) -> None:
        """Start again from the initial state of ``seed``."""
        self.state = None
        self.state = self.init(model_key(seed))

    def put(self, batch):
        return jax.device_put(batch, self.bsh)

    def step(self, batch):
        """Enqueue one step on a placed batch; returns its metrics."""
        self.state, metrics = self.compiled(self.state, batch)
        return metrics


def model_config(cfg: dict, nodes: int):
    """The program's ModelConfig of a configuration file."""
    from repro.configs.registry import get_config
    prog = cfg["program"]
    return dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("overrides", {}), n_nodes=nodes)


def build(mcfg, job: dict, devices: Sequence, seed: int, batch0) -> Program:
    from repro.core.schedule import decaying
    from repro.core.triggers import constant
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq
    from repro.launch.mesh import make_mesh

    devices = list(devices)
    ndev = len(devices)
    rest = ndev // math.gcd(mcfg.n_nodes, ndev)
    model_par = next(m for m in (16, 8, 4, 2, 1) if rest % m == 0)
    prod_mesh = make_mesh((ndev // model_par, model_par), ("data", "model"),
                          devices=devices)
    mesh = sh.train_mesh(prod_mesh, mcfg)
    b, a = job["lr"]
    dcfg = DistSparqConfig(
        H=int(job["H"]), frac=float(job["frac"]), lr=decaying(b, a),
        threshold=constant(float(job["threshold"])),
        variant=job["variant"], use_kernel=True, topology="ring",
        gamma=job.get("gamma"))
    init_fn, train_step, state_specs, _ = build_sparq(mcfg, mesh, dcfg)
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                       is_leaf=lambda x: isinstance(x, P))
    init = jax.jit(init_fn, out_shardings=ssh)
    state = init(model_key(seed))
    bspecs = sh.train_batch_specs(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     batch0), mesh)
    bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                       is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(train_step, in_shardings=(ssh, bsh), donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch0).compile()
    return Program(compiled, train_step, state, bsh,
                   time.perf_counter() - t0, init)


@dataclasses.dataclass
class Window:
    """What a measured window saw: per step, the host seconds spent on its
    batch (build and ``device_put``) and its completion time."""
    t0: float
    done: List[float]
    host_s: List[float]
    first_step: int
    sync_flags: List[bool]
    losses: List[float]

    @property
    def steps(self) -> int:
        return len(self.done)

    @property
    def seconds(self) -> float:
        return self.done[-1] - self.t0

    def durations(self) -> List[float]:
        prev = [self.t0] + self.done[:-1]
        return [d - p for d, p in zip(self.done, prev)]


def drive(prog: Program, batch_of: Callable[[int], dict], first_step: int,
          H: int, seconds: Optional[float] = None,
          steps: Optional[int] = None) -> Window:
    """Run steps from ``first_step`` on, for ``steps`` steps, or for
    ``seconds`` of wall time rounded up to a whole sync period (the window
    then holds syncs in the job's share, so its p90 does not sit on the
    edge between local and sync steps), enqueueing each step before
    waiting on the one before. Host spans: batch, device_put, dispatch,
    wait."""
    ann = jax.profiler.TraceAnnotation
    done, host_s, flags, losses = [], [], [], []
    pending = None
    t0 = time.perf_counter()
    i = first_step
    while True:
        h0 = time.perf_counter()
        with ann("batch"):
            rows = batch_of(i)
        with ann("device_put"):
            placed = prog.put(rows)
        host_s.append(time.perf_counter() - h0)
        with ann("dispatch"):
            metrics = prog.step(placed)
        flags.append((i + 1) % H == 0)
        losses.append(metrics["loss"])
        if pending is not None:
            with ann("wait"):
                jax.block_until_ready(pending)
            done.append(time.perf_counter())
        pending = metrics
        i += 1
        n_run = i - first_step
        if steps is not None and n_run >= steps:
            break
        if (seconds is not None and i % H == 0
                and time.perf_counter() - t0 >= seconds):
            break
    with ann("wait"):
        jax.block_until_ready(pending)
    done.append(time.perf_counter())
    losses = [float(v) for v in jax.device_get(losses)]
    return Window(t0, done, host_s, first_step, flags, losses)


def flat_rows(arr) -> np.ndarray:
    """A node-stacked (n, D_pad) device array on the host."""
    return np.asarray(jax.device_get(arr))


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


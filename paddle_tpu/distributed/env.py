"""Process/cluster environment.

Reference analog: paddle.distributed.init_parallel_env
(python/paddle/distributed/parallel.py:98) — TCPStore rendezvous (:264) +
ProcessGroupNCCL per rank (:272), env contract PADDLE_TRAINER_ID/
PADDLE_TRAINERS_NUM/PADDLE_MASTER set by the launcher.

TPU-native: jax.distributed.initialize IS the coordination service
(≈ TCPStore + comm bootstrap in one); on a TPU pod slice every process
sees its slice-local chips and XLA handles cross-chip routing. Single
process = single "rank" regardless of local chip count (SPMD inside).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from jax import shard_map

_INITIALIZED = False


def init_parallel_env(strategy=None) -> "ParallelEnv":
    """Initialize multi-host coordination if launcher env is present."""
    global _INITIALIZED
    if _INITIALIZED:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER") or \
        os.environ.get("COORDINATOR_ADDRESS")
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM",
                               os.environ.get("NUM_PROCESSES", "1")))
    pid = int(os.environ.get("PADDLE_TRAINER_ID",
                             os.environ.get("PROCESS_ID", "0")))
    if coord and nproc > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    _INITIALIZED = True
    return ParallelEnv()


class ParallelEnv:
    """≈ paddle.distributed.ParallelEnv: rank/world info."""

    @property
    def rank(self) -> int:
        return jax.process_index()

    @property
    def world_size(self) -> int:
        return jax.process_count()

    @property
    def device_id(self) -> int:
        return jax.local_devices()[0].id

    @property
    def nranks(self) -> int:
        return self.world_size

    @property
    def local_rank(self) -> int:
        return self.rank


def get_rank() -> int:
    """Process index (≈ paddle.distributed.get_rank). Note: on TPU one
    process drives many chips; per-chip 'rank' only exists inside
    shard_map via jax.lax.axis_index."""
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def is_initialized() -> bool:
    return _INITIALIZED


def barrier(group=None):
    """Host-level barrier: a tiny psum across all devices."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) == 1:
        return
    import numpy as np
    mesh = Mesh(np.array(devs), ("all",))
    x = jax.device_put(jnp.zeros(len(devs)),
                       NamedSharding(mesh, P("all")))
    shard_map(lambda a: jax.lax.psum(a, "all"), mesh=mesh,
                  in_specs=P("all"), out_specs=P())(x).block_until_ready()

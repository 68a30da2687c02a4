"""Collective communication API.

Reference analog: python/paddle/distributed/collective.py:876-1505
(all_reduce/all_gather/alltoall/broadcast/reduce/scatter/send/recv over
ProcessGroup, C++ side ProcessGroup.h:102-234 and the c_* operator set,
paddle/fluid/operators/collective/).

TPU-native: collectives are XLA ops inside shard_map over a named mesh
axis — ICI-routed, fused and scheduled by the compiler. This module gives
them a paddle-shaped eager API for parity tests and host-driven code
(pipeline schedules); inside pjit-traced model code, USE jax.lax.psum etc.
directly or rely on sharding propagation.

Eager semantics note: `tensor` here is a global jax array sharded over
`axis`; all_reduce(x, axis='dp') psums the shards. ReduceOp maps to the
corresponding XLA collective (c_allreduce_{sum,max,min,prod}_op analogs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import monitor
from jax import shard_map
from ..core.tensor import Tensor
from . import topology


def _count(op: str, axis: str, x):
    """Collective telemetry: per-axis op/byte counters (the reference's
    per-collective stats in the Fleet executor). No-op unless the
    runtime monitor is enabled."""
    if monitor.enabled:
        monitor.record_collective(op, axis, getattr(x, "nbytes", 0))


def _guard(label: str, fn, *args):
    """Launch an eager collective under the hang watchdog when
    PADDLE_WATCHDOG_COLLECTIVE_S sets a deadline (a re-forming slice or
    dead peer can block a collective launch forever on a real pod):
    past the deadline, thread stacks dump to stderr and WatchdogTimeout
    raises instead of hanging. Plain call when unconfigured."""
    from . import resilience
    t = resilience.env_timeout("PADDLE_WATCHDOG_COLLECTIVE_S")
    if t is None:
        return fn(*args)
    return resilience.Watchdog.run(fn, *args, timeout=t,
                                   label=f"collective.{label}")


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


def _mesh(group=None) -> Mesh:
    if group is not None and hasattr(group, "mesh"):
        return group.mesh
    m = topology.get_mesh()
    if m is None:
        # implicit 1-axis mesh over all devices (single-axis "world" group,
        # like paddle's default global group)
        devs = jax.devices()
        m = Mesh(np.array(devs), ("world",))
    return m


def _axis(axis: Optional[str], mesh: Mesh) -> str:
    if axis is not None:
        return axis
    # default: the one non-degenerate axis, else the first
    for name, size in zip(mesh.axis_names, mesh.devices.shape):
        if size > 1:
            return name
    return mesh.axis_names[0]


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def _spec_on(axis, ndim, shard_dim=0):
    if ndim == 0:
        return P()  # scalars are replicated; collectives act on the value
    parts = [None] * ndim
    parts[shard_dim] = axis
    return P(*parts)


def all_reduce(tensor, op: str = ReduceOp.SUM, group=None,
               axis: Optional[str] = None, sync_op=True):
    """Reduce across `axis` shards; every shard gets the result."""
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    x = _raw(tensor)
    _count("all_reduce", ax, x)

    if op == ReduceOp.AVG:
        fn = lambda a: jax.lax.psum(a, ax) / mesh.shape[ax]  # noqa: E731
    elif op == ReduceOp.PROD:
        # no native pprod: gather shards and multiply (sign/zero safe)
        fn = lambda a: jnp.prod(  # noqa: E731
            jax.lax.all_gather(a, ax), axis=0)
    else:
        red = _REDUCERS[op]
        fn = lambda a: red(a, ax)  # noqa: E731

    shard = shard_map(fn, mesh=mesh,
                      in_specs=_spec_on(ax, x.ndim),
                      out_specs=_spec_on(ax, x.ndim), check_vma=False)
    out = _guard("all_reduce", shard, _shard_for(x, mesh, ax))
    result = Tensor(out) if isinstance(tensor, Tensor) else out
    if isinstance(tensor, Tensor):
        tensor._replace_data(out)  # paddle all_reduce is in-place
        return tensor
    return result


def all_gather(tensor_list, tensor, group=None, axis: Optional[str] = None,
               sync_op=True):
    """Gather shards along a new leading-dim list (paddle signature:
    results appended to tensor_list)."""
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    x = _raw(tensor)
    n = mesh.shape[ax]
    _count("all_gather", ax, x)
    fn = shard_map(
        lambda a: jax.lax.all_gather(a, ax),  # [n, ...local shape]
        mesh=mesh, in_specs=_spec_on(ax, x.ndim),
        out_specs=P(*([None] * (x.ndim + 1))),
        check_vma=False)  # all_gather output IS replicated over ax
    gathered = _guard("all_gather", fn, _shard_for(x, mesh, ax))
    if tensor_list is not None:
        tensor_list.extend(Tensor(gathered[i]) for i in range(n))
    return Tensor(gathered)


def broadcast(tensor, src: int = 0, group=None, axis: Optional[str] = None,
              sync_op=True):
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    x = _raw(tensor)
    n = mesh.shape[ax]
    _count("broadcast", ax, x)

    def fn(a):
        # select src's shard and replicate it
        full = jax.lax.all_gather(a, ax)
        return full[src]

    shard = shard_map(fn, mesh=mesh, in_specs=_spec_on(ax, x.ndim),
                      out_specs=_spec_on(ax, x.ndim), check_vma=False)
    out = _guard("broadcast", shard, _shard_for(x, mesh, ax))
    if isinstance(tensor, Tensor):
        tensor._replace_data(out)
        return tensor
    return out


def reduce_scatter(output, input, op: str = ReduceOp.SUM, group=None,
                   axis: Optional[str] = None, sync_op=True):
    """Reduce then scatter along dim 0 (≈ ProcessGroup::ReduceScatter)."""
    if op != ReduceOp.SUM:
        raise NotImplementedError("reduce_scatter supports SUM")
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    x = _raw(input)
    _count("reduce_scatter", ax, x)
    out = _guard("reduce_scatter", shard_map(
        lambda a: jax.lax.psum_scatter(a, ax, scatter_dimension=0,
                                       tiled=True),
        mesh=mesh, in_specs=_spec_on(ax, x.ndim),
        out_specs=_spec_on(ax, x.ndim)), _shard_for(x, mesh, ax))
    if output is not None and isinstance(output, Tensor):
        output._replace_data(out)
        return output
    return Tensor(out)


def alltoall_single(tensor, group=None, axis: Optional[str] = None):
    """Block exchange along dim 0: input sharded over `axis` as n blocks of
    n sub-blocks each; sub-block j of shard i lands as sub-block i of shard
    j (the global_scatter/global_gather primitive,
    operators/collective/global_scatter_op.*)."""
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    x = _raw(tensor)
    _count("alltoall", ax, x)
    out = _guard("alltoall", shard_map(
        lambda a: jax.lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                     tiled=True),
        mesh=mesh, in_specs=_spec_on(ax, x.ndim),
        out_specs=_spec_on(ax, x.ndim)), _shard_for(x, mesh, ax))
    return Tensor(out)


def all_to_all(out_tensor_list, in_tensor_list, group=None,
               axis: Optional[str] = None, sync_op=True):
    """List API (≈ paddle.distributed.alltoall): in the single-controller
    SPMD view, in_tensor_list[j] is the global tensor destined for mesh
    position j, each sharded over `axis` on dim 0 by source."""
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    n = mesh.shape[ax]
    concat = jnp.concatenate([_raw(t) for t in in_tensor_list], axis=0)
    exchanged = alltoall_single(concat, group=group, axis=ax)
    parts = jnp.split(exchanged.data, n, axis=0)
    if out_tensor_list is not None:
        out_tensor_list.extend(Tensor(p) for p in parts)
    return [Tensor(p) for p in parts]


def scatter(tensor, tensor_list=None, src: int = 0, group=None,
            axis: Optional[str] = None):
    mesh = _mesh(group)
    ax = _axis(axis, mesh)
    stacked = jnp.stack([_raw(t) for t in tensor_list]) if tensor_list \
        else _raw(tensor)
    _count("scatter", ax, stacked)
    out = jax.device_put(
        stacked, NamedSharding(mesh, _spec_on(ax, stacked.ndim)))

    def fn(a):
        return a[0]

    res = _guard("scatter", shard_map(
        fn, mesh=mesh, in_specs=_spec_on(ax, stacked.ndim),
        out_specs=_spec_on(ax, stacked.ndim - 1)
        if stacked.ndim > 1 else P(ax)), out)
    if isinstance(tensor, Tensor):
        tensor._replace_data(res)
        return tensor
    return Tensor(res)


def _shard_for(x, mesh, ax):
    """Lay x out sharded on `ax` along dim 0 (replicating over other axes)."""
    if x.shape and x.shape[0] % mesh.shape[ax] == 0:
        return jax.device_put(x, NamedSharding(mesh, _spec_on(ax, x.ndim)))
    return jax.device_put(x, NamedSharding(mesh, P()))


# -------- in-trace helpers (use inside shard_map-ed / pjit code) ----------

def psum(x, axis_name):
    return jax.lax.psum(_raw(x), axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(_raw(x), axis_name)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(_raw(x), axis_name, perm)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)

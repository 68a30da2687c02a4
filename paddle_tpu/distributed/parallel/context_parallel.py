"""Context/sequence parallelism: ring attention + Ulysses (all-to-all).

NEW capability relative to the reference snapshot — SURVEY.md §5 verified
(grep) that Paddle has no sequence/context parallelism; its closest assets
are the fused attention CUDA ops. The TPU design reserves the 'sp' mesh
axis (topology.AXIS_ORDER) and implements the two standard long-context
schemes natively:

- **Ring attention** (`ring_attention`): q/k/v sharded on the sequence dim
  over 'sp'; k/v chunks rotate around the ring via `jax.lax.ppermute`
  (XLA lowers to ICI neighbor exchange) while each device accumulates its
  query block's online softmax — O(S/n) activation memory per chip, full
  overlap of the rotation with the local block matmul. Differentiable: AD
  transposes the ppermute automatically, so the backward runs the reverse
  ring without hand-written collectives.

- **Ulysses** (`ulysses_attention`): all_to_all re-shards sequence →
  heads, runs dense local attention (which may itself use the Pallas
  flash kernel), and all_to_alls back. Cheaper at moderate S, requires
  num_heads % sp == 0.

Both run inside `shard_map` islands so they compose with the dp/mp axes of
the surrounding GSPMD program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import topology

_NEG_INF = -1e30


def _local_block(q, k, v, scale, causal, q_off, k_off):
    """One [sq_local, sk_local] attention block in fp32 online-softmax
    form. Returns (m, l, acc): row max, row normalizer, unnormalized out.
    q/k/v: [B, S_l, H, D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + q_off
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3) + k_off
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                       # [B,H,Q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B,H,Q]
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _merge(carry, new):
    """Merge two online-softmax partial results."""
    m0, l0, a0 = carry
    m1, l1, a1 = new
    m = jnp.maximum(m0, m1)
    c0 = jnp.exp(m0 - m)
    c1 = jnp.exp(m1 - m)
    return m, l0 * c0 + l1 * c1, a0 * c0[..., None] + a1 * c1[..., None]


def _ring_attention_local(q, k, v, *, scale, causal, axis_name):
    """Per-device body under shard_map. q/k/v: [B, S_local, H, D] (their
    shard of the global sequence)."""
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_off = me * s_local
    perm = [(i, (i - 1) % n) for i in range(n)]  # kv source idx advances

    m = jnp.full(q.shape[:1] + (q.shape[2], s_local), _NEG_INF, jnp.float32)
    l = jnp.zeros_like(m)
    acc = jnp.zeros((q.shape[0], q.shape[2], s_local, q.shape[3]),
                    jnp.float32)

    def body(step, carry):
        m, l, acc, k, v = carry
        src = (me + step) % n        # rank whose kv chunk we hold now
        k_off = src * s_local
        if causal:
            # skip chunks strictly above the causal diagonal
            needed = k_off <= q_off + s_local - 1

            def do(args):
                m, l, acc, k, v = args
                return _merge((m, l, acc),
                              _local_block(q, k, v, scale, True,
                                           q_off, k_off))

            m, l, acc = jax.lax.cond(
                needed, do, lambda args: (args[0], args[1], args[2]),
                (m, l, acc, k, v))
        else:
            m, l, acc = _merge((m, l, acc),
                               _local_block(q, k, v, scale, False, 0, 0))
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return m, l, acc, k, v

    m, l, acc, k, v = jax.lax.fori_loop(0, n, body, (m, l, acc, k, v),
                                        unroll=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]                 # [B,H,Q,D]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B,S_l,H,D]


def _axis_degree(mesh, axis_name) -> int:
    return mesh.shape[axis_name] if axis_name in mesh.shape else 1


def _data_spec_entry(mesh, batch):
    axes = [a for a in ("dp", "sharding")
            if _axis_degree(mesh, a) > 1]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return tuple(axes) if axes and batch % n == 0 else None


def ring_attention(q, k, v, causal=False, scale=None,
                   axis_name: str = "sp", mesh=None):
    """Ring attention over [batch, seq, heads, head_dim] GLOBAL arrays
    whose sequence dim is (to be) sharded over `axis_name`. Falls back to
    plain attention when the axis is trivial."""
    mesh = mesh or topology.get_mesh()
    d = q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    if mesh is None or _axis_degree(mesh, axis_name) == 1:
        from ...nn.functional.attention import _sdpa_xla
        return _sdpa_xla(q, k, v, is_causal=causal, scale=scale)
    bspec = _data_spec_entry(mesh, q.shape[0])
    hspec = "mp" if (_axis_degree(mesh, "mp") > 1
                     and q.shape[2] % mesh.shape["mp"] == 0) else None
    spec = P(bspec, axis_name, hspec, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, scale=scale,
                          causal=causal, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, scale, causal, axis_name, sp):
    """Per-device body: [B, S/sp, H, D] → all_to_all → [B, S, H/sp, D] →
    dense attention → back."""
    from ...nn.functional.attention import _sdpa_xla

    def seq_to_heads(x):
        # split heads into sp groups, exchange so each device holds the
        # full sequence for H/sp heads
        x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        return x

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = _sdpa_xla(qh, kh, vh, is_causal=causal, scale=scale)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, causal=False, scale=None,
                      axis_name: str = "sp", mesh=None):
    """DeepSpeed-Ulysses style sequence parallelism: all_to_all seq↔heads.
    Requires num_heads divisible by the sp degree."""
    mesh = mesh or topology.get_mesh()
    d = q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    sp = _axis_degree(mesh, axis_name) if mesh is not None else 1
    if mesh is None or sp == 1:
        from ...nn.functional.attention import _sdpa_xla
        return _sdpa_xla(q, k, v, is_causal=causal, scale=scale)
    if q.shape[2] % sp != 0:
        raise ValueError(
            f"ulysses needs heads {q.shape[2]} divisible by sp={sp}")
    # GQA: k/v are all_to_all'd on the head axis too, so the kv-head
    # count must also divide sp — catch it here with a real message
    # instead of a mid-trace reshape failure
    for name, t in (("key", k), ("value", v)):
        if t.shape[2] % sp != 0:
            raise ValueError(
                f"ulysses needs {name} heads {t.shape[2]} divisible by "
                f"sp={sp}; for GQA either repeat kv heads to a multiple "
                f"of sp or use ring_attention (no head-axis exchange)")
    bspec = _data_spec_entry(mesh, q.shape[0])
    spec = P(bspec, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ulysses_local, scale=scale, causal=causal,
                          axis_name=axis_name, sp=sp),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def split_sequence(x, axis: int = 1, axis_name: str = "sp", mesh=None):
    """Pin a sharding constraint placing `axis` over the sp mesh axis
    (the scatter half of the reference-style scatter/gather SP pair).
    Other dims are left UNCONSTRAINED so existing dp/mp placement
    propagates untouched."""
    mesh = mesh or topology.get_mesh()
    if mesh is None or _axis_degree(mesh, axis_name) == 1:
        return x
    parts = [P.UNCONSTRAINED] * x.ndim
    parts[axis] = axis_name
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def gather_sequence(x, axis: int = 1, axis_name: str = "sp", mesh=None):
    """Constraint-replicate the sequence dim (gather half); other dims
    stay UNCONSTRAINED."""
    mesh = mesh or topology.get_mesh()
    if mesh is None or _axis_degree(mesh, axis_name) == 1:
        return x
    parts = [P.UNCONSTRAINED] * x.ndim
    parts[axis] = None
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))

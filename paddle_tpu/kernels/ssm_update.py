"""The decode-time selective state update of a Mamba-2 mixer: one new
position a lane, on a state matrix a head.

A lane and head hold ``S`` ``[P, N]`` (head width ``P``, state size
``N``) in float32. One step:

    S <- exp(dt A) S + (dt x) (x) B        y = S C

(``D x`` is added by the caller: it needs no state.) The state of a
serving batch is gigabytes (256 lanes x 6 layers x 64 heads x 64 x 128 x
4 B = 3.2 GB), all of it read and written every step, so the step is
bound by that traffic and by nothing else, and what matters is that it
is ONE read and ONE write: the kernel takes the state of ALL layers
stacked, ``[layers, lanes, heads, P, N]``, aliases it to its output
(``input_output_aliases``) and visits only the blocks of its own layer,
so no layer's slice is cut out or put back. XLA's fusion of the same
arithmetic (:func:`ssm_update_reference`) is the path everywhere but on
a TPU, and what the kernel is measured against.

The grid walks the lanes; a block is one lane's heads of one layer (2 MB
at 64 x 64 x 128). Idle lanes are SKIPPED, their state neither read nor
written: the block index of an idle lane is that of the nearest live
lane (``_visits``), and Pallas copies a block only when its index
changes. The small per-lane operands come in the layout the arithmetic
wants them in, made by XLA from a few kilobytes: ``exp(dt A)`` and
``dt x`` with the head along the LANES (``[P, heads]``), so that a
head's column is a static lane slice broadcast over ``N``; ``y`` leaves
through the MXU as ``C S^T``, which lays a group's heads out along the
lanes again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_SUBLANES = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports(state_shape, groups: int, dtype) -> bool:
    """Whether the kernel takes a stacked state of this shape: float32,
    ``N`` in whole lanes, ``P`` in whole sublane tiles, the heads in
    whole groups."""
    _, _, heads, p, n = state_shape
    return (jnp.dtype(dtype) == jnp.float32 and n % 128 == 0
            and p % _SUBLANES == 0 and heads % groups == 0)


def ssm_update_reference(s, x, dt, a, b, c):
    """Plain ``jax.numpy``, float32: ``s`` [lanes, heads, P, N], ``x``
    [lanes, heads, P], ``dt`` [lanes, heads] (after its softplus), ``a``
    [heads] (negative), ``b`` / ``c`` [lanes, groups, N]; head ``i``
    reads group ``i // (heads // groups)``. Returns (``y`` [lanes, heads,
    P], the new ``s``). A lane with ``dt`` = 0 keeps its state."""
    rep = s.shape[1] // b.shape[1]
    bh = jnp.repeat(b, rep, axis=1)
    ch = jnp.repeat(c, rep, axis=1)
    s = (jnp.exp(dt * a)[..., None, None] * s
         + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
    return jnp.sum(s * ch[:, :, None, :], axis=-1), s


def _visits(live):
    """(block lane, action) a grid step: a live lane visits itself and
    updates (1); an idle one stays on the nearest live lane before it
    (after it, for the leading ones) and does nothing (0), so no block
    moves for it. With no lane live every step stays on lane 0, whose
    block the first step hands through (2)."""
    n = live.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, lane, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    src = jnp.where(before >= 0, before, first)
    act = live.astype(jnp.int32)
    act = act.at[0].set(jnp.where(jnp.any(live), act[0], 2))
    return src, act


def _kernel(src_ref, act_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref,
            y_ref, o_ref, *, heads, groups):
    lane = pl.program_id(0)
    act = act_ref[lane]
    rep = heads // groups
    p = s_ref.shape[1]

    @pl.when(act == 1)
    def _update():
        da, dtx = da_ref[...], dtx_ref[...]             # [P, heads]
        for g in range(groups):
            b_row = b_ref[g:g + 1, :]                   # [1, N]
            for h in range(g * rep, (g + 1) * rep):
                o_ref[h] = (da[:, h:h + 1] * s_ref[h]
                            + dtx[:, h:h + 1] * b_row)
            # y of the group's heads, along the lanes: C S^T
            c_rows = jnp.broadcast_to(c_ref[g:g + 1, :],
                                      (_SUBLANES, c_ref.shape[1]))
            s_g = o_ref[g * rep:(g + 1) * rep].reshape(rep * p, -1)
            y = jax.lax.dot_general(
                c_rows, s_g, (((1,), (1,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
            y_ref[g:g + 1, :] = y[:1]

    @pl.when(act != 1)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(act == 2)
    def _hand_through():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("layer",))
def ssm_update(state, layer: int, x, dt, a, b, c, live):
    """``state`` [layers, lanes, heads, P, N] float32, every layer's
    stacked; the rows of ``layer`` are updated IN PLACE for the lanes
    that are ``live`` [lanes] bool (the others keep theirs and give
    ``y`` = 0). ``x``, ``dt``, ``a``, ``b``, ``c`` as
    :func:`ssm_update_reference` takes them. Returns (``y`` [lanes,
    heads, P] float32, the stacked state)."""
    _, lanes, heads, p, n = state.shape
    groups = b.shape[1]
    if not supports(state.shape, groups, state.dtype):
        raise ValueError(f"ssm_update does not take a state of "
                         f"{state.shape} {state.dtype} in {groups} groups: "
                         "see supports()")
    f32 = jnp.float32
    dt = dt.astype(f32)
    # [lanes, P, heads]: the head along the lanes (module docstring)
    da = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[:, None, :],
                          (lanes, p, heads))
    dtx = jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2)
    src, act = _visits(live)
    rep = heads // groups

    def state_index(i, src, act):
        return (layer, src[i], 0, 0, 0)

    def lane_index(i, src, act):
        return (i, 0, 0)

    per_lane = lambda *shape: pl.BlockSpec((None,) + shape, lane_index)
    block = pl.BlockSpec((None, None, heads, p, n), state_index)
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[block, per_lane(p, heads), per_lane(p, heads),
                      per_lane(groups, n), per_lane(groups, n)],
            out_specs=[per_lane(groups, rep * p), block]),
        out_shape=[jax.ShapeDtypeStruct((lanes, groups, rep * p), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 2 (after the two prefetched lists) is the state
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * heads * p * n * 4 + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=6 * lanes * heads * p * n,
            bytes_accessed=2 * lanes * heads * p * n * 4,
            transcendentals=0),
        interpret=_interpret(),
        name="ssm_update",
    )(src, act, state, da, dtx, b.astype(f32), c.astype(f32))
    return y.reshape(lanes, heads, p), state

"""Grouped matrix product for sparse experts: ``lhs`` rows sorted by
group, one weight matrix a group (what ``jax.lax.ragged_dot`` computes).

Why a kernel of the repo's own. A decode step hands an expert 16-32
rows, so the product is bound by the bytes of the weights, and XLA's
grouped-product op takes 2.3-2.6 times as long as this kernel for them:
27-36% of the HBM roofline at 4 rows a group as at 256, its time going
by the groups that hold a row (PERF.md section 5 has the table, from
``experiments/grouped_matmul_bench.py`` on the chip). Here a visit
copies one group's ``[K, N]`` block, megabytes at a time, while the
last one is multiplied: 75-88% of the roofline at the serving cells'
shapes. The row tile hardly matters to the time (32 to 256 rows a tile
read within 3%): a visit costs its copy. 128 rows keep the visits, (row
tiles + groups that hold a row) of them, near the number of groups.

The pattern is the paged decode kernel's (``flash_attention.py``): a
work list in scalar-prefetch memory, built by a few jnp ops from
``group_sizes``, of the (row tile, group) visits that hold at least one
row, walked in order by the grid. The weight block's index map names
the group, so consecutive visits of one group fetch it once, and an
empty group is in no visit: it costs nothing and is not read. Rows of a
tile that belong to another group are masked at the store; the output
tile stays in VMEM while consecutive visits fill it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_TILE = 128               # rows a visit computes (the module doc)
_WEIGHT_BLOCK_BYTES = 8 << 20  # most weight bytes one visit copies
_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def row_tile(m: int) -> int:
    """Rows a visit computes: 128, or all ``m`` rows where there are
    fewer."""
    return min(_ROW_TILE, m)


def col_tile(k: int, n: int, itemsize: int = 2,
             limit: int = _WEIGHT_BLOCK_BYTES) -> int:
    """Columns of a weight block ``[k, tn]``: the widest multiple of 128
    that divides ``n`` and keeps the block within ``limit`` bytes, so
    that the 0.35 us a grid step costs is a few percent of its copy (all
    of ``n`` at 2048 x 1536 and 768 x 2048, half of it at 2048 x 3584).
    0 where even 128 columns do not fit."""
    fits = [t for t in range(_LANES, n + 1, _LANES)
            if n % t == 0 and k * t * itemsize <= limit]
    return max(fits, default=0)


def supports(m: int, k: int, n: int, itemsize: int = 2) -> bool:
    """Whether the kernel takes these shapes: whole row tiles of whole
    sublane tiles, ``k`` and ``n`` in whole lanes, a weight block that
    fits. The caller keeps ``ragged_dot`` for the rest."""
    tm = row_tile(m)
    return (m > 0 and m % tm == 0 and tm % 16 == 0 and k % _LANES == 0
            and n % _LANES == 0 and col_tile(k, n, itemsize) > 0)


def work_list(group_sizes, m: int, tm: int):
    """(group, tile, start, end, count): the (row tile, group) visits that
    hold a row, in row order, padded to the static ``m // tm + groups``;
    each group's first row and the row past its last; how many visits
    there are. Rows past ``sum(group_sizes)`` are one more group (id
    ``groups``) that owns no row (``start == end``), so that their tiles
    are visited and stored as zeros, as ``ragged_dot`` leaves them. A few
    small fusions on ``[visits, groups]`` ints, the same for both
    products of a layer."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    sizes = jnp.concatenate([sizes, (m - jnp.sum(sizes))[None]])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    visit = jnp.arange(m // tm + e, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= upto[None, :], axis=1, dtype=jnp.int32),
        e)
    tile = jnp.minimum(first[group] + visit - (upto - tiles)[group],
                       m // tm - 1)
    owned = jnp.arange(e + 1) < e       # the tail owns nothing
    return (group, tile, jnp.where(owned, starts, m),
            jnp.where(owned, ends, m), upto[-1])


def _kernel(group_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
            out_ref, *, tm):
    v = pl.program_id(1)
    g, t = group_ref[v], tile_ref[v]
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = jnp.logical_and(row >= start_ref[g], row < end_ref[g])
    # a tile's first visit finds whatever the buffer held before
    opens = jnp.logical_or(v == 0, tile_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(opens)
    def _first():
        out_ref[...] = jnp.where(mine, acc, 0.0)

    @pl.when(jnp.logical_not(opens))
    def _fill():
        out_ref[...] = jnp.where(mine, acc, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tiles",))
def _pallas(lhs, rhs, group_sizes, tiles=None):
    # jitted so that a model's expert layers, all of one shape, trace the
    # kernel once and not once a layer and program (36 ms each: a second
    # of set-up at 12 layers). ``tiles``: the microbenchmark's sweep alone
    # (experiments/grouped_matmul_bench.py); callers get the shapes' own
    m, k = lhs.shape
    e, _, n = rhs.shape
    tm, tn = tiles or (row_tile(m), col_tile(k, n, rhs.dtype.itemsize))
    group, tile, start, end, count = work_list(group_sizes, m, tm)

    def lhs_index(j, v, group, tile, start, end):
        return (tile[v], 0)

    def rhs_index(j, v, group, tile, start, end):
        return (jnp.minimum(group[v], e - 1), 0, j)

    def out_index(j, v, group, tile, start, end):
        return (tile[v], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, count),
        in_specs=[pl.BlockSpec((tm, k), lhs_index),
                  pl.BlockSpec((None, k, tn), rhs_index)],
        out_specs=pl.BlockSpec((tm, tn), out_index),
    )
    # every block is double buffered; the product and the select hold
    # two more [tm, tn] float32 values
    blocks = 2 * (tm * k * lhs.dtype.itemsize + k * tn * rhs.dtype.itemsize
                  + tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=blocks + 2 * tm * tn * 4 + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=(e * k * n * rhs.dtype.itemsize
                            + (n // tn) * m * k * lhs.dtype.itemsize
                            + m * n * 4),
            transcendentals=0),
        interpret=_interpret(),
        name="grouped_matmul",
    )(group, tile, start, end, lhs, rhs)


def ragged_dot(lhs, rhs, group_sizes):
    """XLA's own grouped product, float32 off the accumulator: what the
    kernel equals, its gradient, and the caller's path for the shapes
    and backends the kernel does not take."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [M, K] with its rows sorted by group, ``rhs`` [E, K, N],
    ``group_sizes`` [E] int32 -> [M, N] float32: row ``i`` of group ``g``
    times ``rhs[g]``, accumulated in float32 over the whole of K, rows
    past ``sum(group_sizes)`` zero: what ``jax.lax.ragged_dot(...,
    preferred_element_type=float32)`` gives. The shapes have to pass
    :func:`supports`. The gradient is ``ragged_dot``'s own, on the saved
    operands."""
    if not supports(lhs.shape[0], lhs.shape[1], rhs.shape[2],
                    rhs.dtype.itemsize):
        raise ValueError(
            f"grouped_matmul does not take lhs {lhs.shape} x rhs "
            f"{rhs.shape}: see supports()")
    return _pallas(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(saved, g):
    lhs, rhs, group_sizes = saved
    _, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), None)


grouped_matmul.defvjp(_fwd, _bwd)

"""The new positions' K and V rows into the stacked page pool: one
aliased write a layer.

A serving step appends ``s`` positions a lane (1 in plain decode, a
block of 4 under block diffusion, a verify window of up to 8) to the pool
``[layers, n_pages, kv_heads, page_size, head_dim]``: ``kv_heads * s``
rows of 256 B a lane, each in another page-and-head run of the pool.
XLA's row scatter (``paged_cache._scatter_tokens``) takes about 70 ns a
row whatever the pool's size, 141 us for the 2,048 rows of 64 lanes x 32
heads, whose half megabyte needs 0.6 us of HBM time. This kernel takes
the pools of K and V whole, aliases them to its outputs
(``input_output_aliases``, as ``ssm_update`` takes its state) and visits
one lane a grid step: the block is the sublane tile of the lane's page
that holds its new rows, all kv heads of it (``[kv_heads, T, head_dim]``
at ``(layer, page, 0, off // T, 0)``; ``T`` rows are 16 of bfloat16, 8
of float32). The tile is copied in, the new rows are put over theirs,
and the tile is written back: a bfloat16 row is half of a packed
sublane, so a tile is the least the chip writes whole. Its cost goes by
the VISITS (a lane each), the scatter's by the ROWS, so
:func:`supports` hands the scatter every shape with few rows a lane.

- **Two visits a lane where ``s > 1``**: a window starts at the lane's
  ``kv_len``, which is any number, so it can straddle a tile or a page;
  ``s <= T`` keeps it to two tiles. The second visit is live only where
  the window's last position lies in another tile than its first.
- **Idle visits move nothing**: a lane that writes to the null page 0
  (an idle lane, a position past the table: ``_write_pages`` sends both
  there) is not written at all, which the null page's contract allows
  (nothing reads it unmasked). Its grid step stays on the block of the
  nearest live visit (``ssm_update._visits``: an idle visit does
  nothing, and with no visit live the first hands its block through),
  and Pallas copies a block only when its index changes.
- **No two live lanes share a tile**: decode writes start at a row's own
  ``kv_len`` in a page it owns alone (copy-on-write at admission:
  ``paged_cache``'s docstring), so no visit reads a tile that an earlier
  one has yet to write back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# which block an idle grid step stays on: the rule is the state kernel's,
# with a visit in a lane's place
from .ssm_update import _visits

_LANES = 128
# the fewest rows a lane (kv heads x positions) the kernel takes. On the
# v5e (experiments/kv_write_bench.py; PERF.md section 5) a visit costs
# 0.3-1.0 us and a scattered row 0.15-0.19 us (K and V): at 4 rows a
# lane the kernel takes half the scatters' time, at 2 it is 15% under
# them, at 1 half over them
MIN_ROWS_A_LANE = 4


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sublane_tile(dtype) -> int:
    """Rows of the least tile the chip writes whole: 8 sublanes of 32
    bits, two bfloat16 rows packed in each."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _fits(pool_shape, dtype, s: int) -> bool:
    """Whether the kernel can take ``s`` new positions a lane into a pool
    of this shape and dtype at all: bfloat16 or float32 values,
    ``head_dim`` in whole lanes, pages in whole tiles, a window inside
    two tiles."""
    _, _, _, page_size, d = pool_shape
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    t = sublane_tile(dtype)
    return d % _LANES == 0 and page_size % t == 0 and 1 <= s <= t


def supports(pool_shape, dtype, s: int) -> bool:
    """Whether :func:`paged_kv_write` is the path for ``s`` new positions
    a lane into a pool of this shape and dtype: on a TPU, a pool the
    kernel can take (the int8 pool and its scale sidecars keep the
    scatter), and rows enough a lane."""
    return (jax.default_backend() == "tpu" and _fits(pool_shape, dtype, s)
            and pool_shape[2] * s >= MIN_ROWS_A_LANE)


def _kernel(page_ref, tile_ref, lane_ref, act_ref, row_ref, k_ref, v_ref,
            kn_ref, vn_ref, ko_ref, vo_ref, *, s, visits):
    lane, w = pl.program_id(0), pl.program_id(1)
    act = act_ref[lane * visits + w]
    heads, t, d = k_ref.shape

    @pl.when(act == 1)
    def _merge():
        # position j of the window lies in row ``first + j`` of this
        # visit's tile, if that is a row of it
        first = row_ref[lane * visits + w]
        rows = jax.lax.broadcasted_iota(jnp.int32, (t, d), 0)
        at = [rows == first + j for j in range(s)]
        for src, new, dst in ((k_ref, kn_ref, ko_ref),
                              (v_ref, vn_ref, vo_ref)):
            fresh = [new[j] for j in range(s)]          # [heads, d] each
            for h in range(heads):
                out = src[h]
                for j in range(s):
                    row = jnp.broadcast_to(fresh[j][h:h + 1], (t, d))
                    out = jnp.where(at[j], row, out)
                dst[h] = out

    @pl.when(act == 2)
    def _hand_through():
        ko_ref[...] = k_ref[...]
        vo_ref[...] = v_ref[...]


@functools.partial(jax.jit, static_argnames=("layer",))
def paged_kv_write(k, v, layer: int, page, off, k_new, v_new):
    """``k`` / ``v`` [layers, n_pages, kv_heads, page_size, D], every
    layer's stacked; ``k_new`` / ``v_new`` [batch, s, kv_heads, D] go to
    ``(layer, page[i], :, off[i])`` IN PLACE for the flat position ``i``,
    ``page`` / ``off`` [batch * s] int32 as ``PagedKVCache._token_dest``
    gives them (a lane's positions consecutive). Whatever goes to page 0
    is left out. Returns the stacked (``k``, ``v``). Off a TPU the kernel
    runs interpreted."""
    _, _, heads, _, d = k.shape
    b, s = k_new.shape[:2]
    if (not _fits(k.shape, k.dtype, s) or (v.shape, v.dtype)
            != (k.shape, k.dtype) or k_new.shape != (b, s, heads, d)
            or v_new.shape != k_new.shape):
        raise ValueError(
            f"paged_kv_write does not take new rows {k_new.shape} / "
            f"{v_new.shape} for pools {k.shape} {k.dtype} / {v.shape} "
            f"{v.dtype}: see supports()")
    t = sublane_tile(k.dtype)
    page, off = page.reshape(b, s), off.reshape(b, s)
    # a window lies in its first position's tile and, where it straddles,
    # in its last position's
    visits = 1 if s == 1 else 2
    ends = (0, s - 1)[:visits]
    vpage = jnp.stack([page[:, j] for j in ends], axis=1)
    vtile = jnp.stack([off[:, j] // t for j in ends], axis=1)
    live = vpage != 0
    if visits == 2:
        moved = (vpage[:, 1] != vpage[:, 0]) | (vtile[:, 1] != vtile[:, 0])
        live = live.at[:, 1].set(live[:, 1] & moved)
    src, act = _visits(live.reshape(-1))
    vpage, vtile = vpage.reshape(-1)[src], vtile.reshape(-1)[src]
    vlane = src // visits
    # the row of the visit's tile in which the window's position 0 would
    # lie (below 0 in the second tile)
    row = jnp.stack([off[:, j] % t - j for j in ends], axis=1).reshape(-1)

    def pool_index(i, w, page, tile, lane, act, row):
        at = i * visits + w
        return (layer, page[at], 0, tile[at], 0)

    def new_index(i, w, page, tile, lane, act, row):
        return (lane[i * visits + w], 0, 0, 0)

    pool = pl.BlockSpec((None, None, heads, t, d), pool_index)
    new = pl.BlockSpec((None, s, heads, d), new_index)
    tile_bytes = heads * t * d * k.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, s=s, visits=visits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, visits),
            in_specs=[pool, pool, new, new],
            out_specs=[pool, pool]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        # operands 5 and 6 (after the five prefetched lists) are the pools
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=4 * b * tile_bytes),
        interpret=_interpret(),
        name="paged_kv_write",
    )(vpage, vtile, vlane, act, row, k, v,
      k_new.astype(k.dtype), v_new.astype(v.dtype))

"""A cache that holds per-lane states of fixed width beside keys and values.

A model whose layers are not all attention needs more of a cache than
keys and values: a recurrent or convolutional mixer carries states of
FIXED width from one position to the next (a gated short convolution of
kernel length ``L``: the last ``L - 1`` columns of its gated input; a
state-space mixer: that window of its convolution AND a state matrix a
head, in float32), a lane and layer each. :class:`HybridCache` puts
those states beside a KV cache of any kind this package has and speaks
the same protocol, so the model stack, the serving engine's programs and
the page allocator drive it unchanged:

- ``kv``: a :class:`~.kv_cache.KVCache`, :class:`~.kv_cache.QuantKVCache`,
  :class:`~.paged_cache.PagedKVCache` or
  :class:`~.paged_cache.QuantPagedKVCache` over the ATTENTION layers
  only (its layer axis counts them, not the model's blocks);
- ``state``: a TUPLE of arrays, one for each state a stateful mixer
  carries, each ``[state layers, batch, *shape]`` in its own dtype: one
  row a lane in every layer that carries state.

Everything about keys and values (``update``, ``positions``, ``kv_len``,
``with_kv_len``, ``page_table``, ``k`` / ``v`` and their scales,
``occupancy``) is the inner cache's, reached by delegation. A mixer reads
its ``j``-th state with ``cache.state[j][layer]`` and hands the next ones
back through :meth:`with_state` (a layer's rows) or :meth:`with_stacked`
(the whole stacked array, from a kernel that updated its layer's rows IN
PLACE: a state of gigabytes must not be copied a layer).
``install_row`` / ``reset_rows`` move or clear a lane's states together
with its KV row, which is what keeps one request's state out of the next
one's slot.

What a state cannot do, and what the engine therefore refuses for a
model that has one (``ServingEngine.__init__``): be rolled back to an
earlier length (speculative verify windows rewind ``kv_len``), or be
re-entered at a page boundary from another request's prefix (the engine
never skips a prefill for a shared prefix, so prefix sharing of the PAGES
stays sound: the state is always computed from the whole prompt).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kv_cache import _raw

__all__ = ["HybridCache", "window_state"]


def window_state(prior, window, valid):
    """The state a mixer hands on after a window: the last ``n`` columns
    before position ``valid`` of ``window`` ([b, s, H]), reaching back
    into ``prior`` ([b, n, H]: the state before the window) where the
    window holds fewer than ``n`` real positions. ``valid`` [b] int32
    counts the window's real positions (a prefill runs at a padded
    bucket: what lies past ``prompt_len`` is padding and must not enter
    the state)."""
    n = prior.shape[1]
    both = jnp.concatenate([prior.astype(window.dtype), window], axis=1)
    valid = jnp.clip(jnp.asarray(valid, jnp.int32), 0, window.shape[1])
    # row r: both[r, valid[r] : valid[r] + n]
    idx = valid[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(both, idx[:, :, None], axis=1)


@jax.tree_util.register_pytree_node_class
class HybridCache:
    """KV cache ``kv`` over the attention layers + the per-lane ``state``
    arrays over the layers that carry them (module docstring)."""

    __slots__ = ("kv", "state")

    def __init__(self, kv, state):
        self.kv = kv
        self.state = tuple(state)

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.kv, self.state), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __getattr__(self, name):
        # k, v, kv_len, page_table, k_scale, clips, max_len, batch, ...:
        # whatever the inner cache has; what it lacks stays lacking
        # (``getattr(cache, "page_table", None)`` is how callers ask)
        if name in HybridCache.__slots__:
            raise AttributeError(name)
        return getattr(self.kv, name)

    @classmethod
    def create(cls, kv, state_layers: int, specs, dtype):
        """``kv`` beside zero states (no position seen yet). ``specs``:
        a ``(shape, dtype)`` for each state a mixer carries; a dtype of
        None is ``dtype``, the activations' own."""
        return cls(kv, tuple(
            jnp.zeros((state_layers, kv.batch) + tuple(shape), dt or dtype)
            for shape, dt in specs))

    def paged(self, n_pages: int, page_size: int, pages_per_row: int):
        """This cache's avals with the KV rows replaced by a page pool
        (``KVCache.paged``): every state stays one row a lane."""
        return HybridCache(
            self.kv.paged(n_pages, page_size, pages_per_row), self.state)

    @property
    def state_bytes(self) -> int:
        return sum(int(s.size) * jnp.dtype(s.dtype).itemsize
                   for s in self.state)

    # ----------------------------------------------------- the KV half
    def update(self, layer: int, k_new, v_new, pos) -> "HybridCache":
        return HybridCache(self.kv.update(layer, k_new, v_new, pos),
                           self.state)

    def with_kv_len(self, kv_len) -> "HybridCache":
        return HybridCache(self.kv.with_kv_len(kv_len), self.state)

    def install_span(self, src: "HybridCache", table_row,
                     start) -> "HybridCache":
        return HybridCache(self.kv.install_span(src.kv, table_row, start),
                           self.state)

    # ---------------------------------------------------- the state half
    def with_state(self, layer: int, new) -> "HybridCache":
        """Layer ``layer``'s states replaced: ``new`` holds one
        ``[batch, *shape]`` for each state, or None for one that stays."""
        return HybridCache(self.kv, tuple(
            s if n is None else s.at[layer].set(_raw(n).astype(s.dtype))
            for s, n in zip(self.state, new)))

    def with_stacked(self, which: int, stacked) -> "HybridCache":
        """State ``which`` replaced whole, all layers stacked: what a
        kernel hands back that rewrote one layer's rows of its aliased
        operand."""
        state = list(self.state)
        state[which] = _raw(stacked)
        return HybridCache(self.kv, state)

    # -------------------------------------------------------- slot reuse
    def install_row(self, src: "HybridCache", slot,
                    *where) -> "HybridCache":
        """Slot admission: the batch-1 prefill cache ``src`` becomes row
        ``slot``, its KV row as the inner cache installs one (``where``:
        a page pool's table row and first written position) and its
        states, whole, over whatever the slot's last holder left."""
        slot = jnp.asarray(_raw(slot), jnp.int32)
        return HybridCache(
            self.kv.install_row(src.kv, slot, *where),
            tuple(s.at[:, slot].set(r[:, 0].astype(s.dtype))
                  for s, r in zip(self.state, src.state)))

    def reset_rows(self, rows) -> "HybridCache":
        """Free rows for reuse: the inner cache severs their KV, and
        their states go back to zero (no position seen)."""
        rows = jnp.asarray(_raw(rows))

        def zero(s):
            if rows.dtype == jnp.bool_:
                keep = ~rows.reshape((1, -1) + (1,) * (s.ndim - 2))
                return jnp.where(keep, s, 0)
            return s.at[:, rows].set(0)

        return HybridCache(self.kv.reset_rows(rows),
                           tuple(zero(s) for s in self.state))

    def __repr__(self):
        states = ", ".join(f"{tuple(s.shape)} {s.dtype}"
                           for s in self.state)
        return f"HybridCache({self.kv!r}, state=({states}))"

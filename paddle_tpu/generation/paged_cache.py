"""Block-table paged KV cache with shared-prefix reuse.

The dense ring cache (``kv_cache.KVCache``) charges every slot
``max_len`` HBM whether its request uses 40 tokens or 4000, and N
concurrent requests sharing a system prompt each store their own copy
of its K/V. This module replaces the per-slot rows with a POOL of
fixed-size pages plus per-row page tables — the PagedAttention design
(Kwon et al., SOSP '23) with Hydragen-style shared-prefix reuse —
built natively on the decode kernel's index-map indirection (the same
mechanism its GQA head mapping already uses):

- **PagedKVCache** (device pytree): ``k, v`` pools of shape
  ``[layers, n_pages, heads, page_size, head_dim]``, a per-row int32
  ``page_table [batch, pages_per_row]``, and the familiar per-row
  ``kv_len``. ``update``/``install_row``/``reset_rows`` are
  pure-functional (donated in the engine's compiled programs, same as
  the dense cache) and every write resolves its destination page
  through the table in-trace — the page ids are DATA, so one compiled
  program serves every allocation layout.
- **Why tokens sit on the second-minor axis.** The TPU tiles an
  array's two minor dimensions, so ``(page_size, head_dim)`` minor puts
  tokens on sublanes and ``head_dim`` on lanes: one page of one head is
  one contiguous run of tiles, exactly the ``(page, head_dim)`` block
  the paged decode kernel streams. The kernel's index map picks
  ``(layer, page id, head)`` out of the STACKED pool, so no program
  ever slices a layer out of the pool or transposes it (with heads on
  sublanes, ``[.., page, heads, head_dim]``, both copies were forced:
  52 ms of a 110 ms decode step at 6.7B widths: PERF.md section 6,
  PR 27). A page stays a whole unit of ``heads * page_size *
  head_dim`` contiguous elements per layer, so everything that works
  on page ids (prefix sharing, copy-on-write, reclaim) is untouched by
  the order inside a page.
- **Two paths write a step's new rows, chosen by shape** (``update``).
  On a TPU, a bfloat16 or float32 pool whose lanes each write 4 rows or
  more (``kv_heads`` x positions) takes ``kernels/paged_write.py``: K
  and V of a layer in ONE kernel, the stacked pools aliased to its
  outputs, a lane's sublane tile read, merged and written back a grid
  step, idle lanes skipped (62 us a layer where the scatters took 288
  at 64 lanes x 32 heads: PERF.md section 5, PR 35).
  ``paged_write.supports`` reads backend, dtype and shapes, nothing
  else. Everything else (the CPU, the int8 pool and its scale sidecars,
  2 rows a lane, a window longer than a tile) takes ``_scatter_tokens``,
  one XLA scatter for K and one for V, about 70-95 ns a row. Its
  **indices enumerate layer and head**, leaving ``head_dim`` as the
  only window dimension: a scatter whose window spans ``heads`` and
  ``head_dim`` (``pool.at[l, page, :, off]``) makes XLA's layout
  assignment flip the whole stacked pool around every write, two
  pool-sized copies per token. ``tests/test_tpu_compile.py`` holds the
  compiled programs of both paths to no pool-sized temporary.
- **Page 0 is the reserved null page**: masked install positions,
  out-of-table positions, and idle engine lanes (``kv_len == 0``, the
  finished-slot contract) all route their writes there. Nothing ever
  reads it unmasked — this is what makes a parked slot with a stale
  table harmless while its pages are already re-owned by another row.
- **PageAllocator** (host): free-list allocation, per-page refcounts,
  and a prompt-prefix registry hashed at page granularity — an
  admission whose leading full pages hash-match a registered prompt
  REFERENCES those pages (prefill once, reference-count many) instead
  of storing a private copy. A prompt diverging INSIDE a shared page
  (its tail is a partial page of a fully-matched prefix) gets a
  private copy-on-write page at admission — the only moment a write
  could land on shared content, because full prompt pages are never
  written after install and decode writes always start at the row's
  own ``kv_len``. Registered pages with refcount 0 stay cached for
  future prefix hits and are reclaimed LRU when allocation runs dry.

Host syncs: the allocator runs entirely on host metadata (page ids,
token hashes) — it never touches device arrays; the only device reads
on this path stay the engine's existing poll-cadence lane reads.

Reference analog: the reference's serving layer keeps contiguous
CacheKV tensors per request (fused_multi_transformer); vLLM proved the
block-table form is what survives real traffic. Here the table rides
the same BlockSpec/SMEM machinery as the per-row ``kv_len``.
"""
from __future__ import annotations

import collections
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monitor as _monitor
from ..kernels import paged_write as _paged_write
from .kv_cache import KVCache, _raw, quantize_kv, validate_cache_dtype

__all__ = ["PagedKVCache", "QuantPagedKVCache", "PageAllocator",
           "AdmissionPlan"]


def pool_head_dim(head_dim: int) -> int:
    """The pools' minor dimension for heads of ``head_dim``: heads of 64
    are stored in 128 lanes, the upper half zero. The TPU tiles an
    array's two minor dimensions in (sublanes, 128 lanes): it lays a
    ``[.., 128, 64]`` array out with the 64 SECOND-minor (no lane is
    wasted that way), the paged decode kernel wants ``head_dim`` minor
    (in 128-lane tiles: it reads 128 lanes a row whatever they hold),
    and the copy between the two layouts is the whole pool, K and V, in
    every attention layer of every step (1.6 GB of scratch and 8.6 ms a
    step at 3 layers of 8 heads: PERF.md section 6, PR 32). Padded, the
    array the kernel reads is the array the program holds. Other widths
    below a lane tile never reach that kernel and stay as they are."""
    return 128 if head_dim == 64 else head_dim


def _to_pool_width(new, buf):
    """``new`` ([..., head_dim]) zero-padded to the pool's lanes."""
    short = buf.shape[-1] - new.shape[-1]
    if short == 0:
        return new
    return jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, short),))


def _scatter_tokens(buf, layer: int, page, off, new):
    """Write ``new`` ([batch, s, heads, ...]) into ``buf`` ([layers,
    n_pages, heads, page_size, ...]) at ``(layer, page[i], :, off[i])``
    for the flat token ``i``. Layer and head are enumerated in the
    indices, not spanned by the window (see the module docstring)."""
    heads = jnp.arange(buf.shape[2], dtype=jnp.int32)[None, :]
    flat = new.reshape((-1,) + new.shape[2:]).astype(buf.dtype)
    if buf.ndim == 5:       # values (a scale sidecar has no head_dim)
        flat = _to_pool_width(flat, buf)
    return buf.at[layer, page[:, None], heads, off[:, None]].set(flat)


def _install_pages(buf, rows, page, valid):
    """Write the positions ``valid`` ([n * page_size] bool) of the
    batch-1 dense rows ``rows`` ([layers, 1, t, heads, ...], ``t`` at
    most ``n * page_size``) into the pages ``page`` ([n] int32) of
    ``buf``, whole pages at a time: gather the n pages, merge, scatter
    them back with (layer, page) in the indices and the page itself,
    ``heads * page_size * head_dim`` contiguous elements, as the window.
    Positions not ``valid`` keep what the page held. (Token by token, as
    ``_scatter_tokens`` writes, an admission is 262,144 rows of 256 B at
    6.7B widths and took 68 ms on the v5e: PERF.md section 6, PR 27.)"""
    n, ps = page.shape[0], buf.shape[3]
    rows = rows[:, 0]
    if buf.ndim == 5:       # values (a scale sidecar has no head_dim)
        rows = _to_pool_width(rows, buf)
    new = jnp.pad(rows, ((0, 0), (0, n * ps - rows.shape[1]))
                  + ((0, 0),) * (rows.ndim - 2))
    new = new.reshape((rows.shape[0], n, ps) + rows.shape[2:])
    new = jnp.swapaxes(new, 2, 3).astype(buf.dtype)  # [L, n, H, ps, ..]
    keep = valid.reshape((1, n, 1, ps) + (1,) * (buf.ndim - 4))
    layers = jnp.arange(buf.shape[0], dtype=jnp.int32)[:, None]
    at = (layers, page[None, :])
    return buf.at[at].set(jnp.where(keep, new, buf[at]))


@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Paged K/V pool + per-row page tables + per-row valid lengths.

    Implements the decode half of the KV-cache protocol (``update``,
    ``positions``, ``with_kv_len``, ``reset_rows``, ``kv_len``) so the
    model stack and the speculative verify core drive it unchanged;
    prefill stays on the dense batch-1 row cache, which
    ``install_row`` then scatters into the pool through the table.
    """

    __slots__ = ("k", "v", "page_table", "kv_len")

    def __init__(self, k, v, page_table, kv_len):
        self.k = k
        self.v = v
        self.page_table = page_table
        self.kv_len = kv_len

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.k, self.v, self.page_table, self.kv_len), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ------------------------------------------------------------- shape
    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def batch(self) -> int:
        return self.page_table.shape[0]

    @property
    def pages_per_row(self) -> int:
        return self.page_table.shape[1]

    @property
    def max_len(self) -> int:
        """Logical per-row capacity (the dense cache's ``max_len``)."""
        return self.pages_per_row * self.page_size

    @property
    def dtype(self):
        return self.k.dtype

    @property
    def cache_dtype(self):
        """The declared low-bit storage mode (None = full width)."""
        return None

    # ---------------------------------------------------------- creation
    @classmethod
    def create(cls, num_layers: int, batch: int, n_pages: int,
               page_size: int, pages_per_row: int, num_heads: int,
               head_dim: int, dtype=jnp.float32,
               cache_dtype=None) -> "PagedKVCache":
        shape = (num_layers, n_pages, num_heads, page_size,
                 pool_head_dim(head_dim))
        if validate_cache_dtype(cache_dtype) is not None:
            sshape = shape[:-1]
            return QuantPagedKVCache(
                jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros((batch, pages_per_row), jnp.int32),
                jnp.zeros((batch,), jnp.int32),
                jnp.zeros(sshape, jnp.bfloat16),
                jnp.zeros(sshape, jnp.bfloat16),
                jnp.zeros((), jnp.int32))
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((batch, pages_per_row), jnp.int32),
                   jnp.zeros((batch,), jnp.int32))

    # ------------------------------------------------------------ update
    def _write_pages(self, pos):
        """(page, offset) destinations for per-row write positions
        ``pos`` ([batch, s] int32): resolve through the table, routing
        idle lanes (row write base 0 — the engine pins finished slots'
        kv_len to 0) and out-of-table positions to the null page 0."""
        slot = pos // self.page_size
        page = jnp.take_along_axis(
            self.page_table,
            jnp.minimum(slot, self.pages_per_row - 1), axis=1)
        dead = (pos[:, 0:1] == 0) | (slot >= self.pages_per_row)
        return jnp.where(dead, 0, page), pos % self.page_size

    def _token_dest(self, pos, b: int, s: int):
        """Flat ([b * s]) (page, offset) of ``s`` tokens appended per
        row at start position ``pos`` (scalar or [b])."""
        pos = jnp.asarray(_raw(pos), jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (b,))
        positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        page, off = self._write_pages(positions)          # [b, s] each
        return page.reshape(-1), off.reshape(-1)

    def _span_dest(self, src, table_row, start):
        """Where the batch-1 dense row ``src`` goes under ``table_row``,
        page by page: ``(page [n], valid [n * page_size])``, ``n`` pages
        covering ``src.max_len``. Only positions in ``[start, src.kv_len[0])`` and inside
        the table are valid; a page with none goes to the null page
        (a shared prefix page is referenced, never re-written)."""
        ps = self.page_size
        n = -(-src.max_len // ps)
        pos = jnp.arange(n * ps, dtype=jnp.int32)
        valid = ((pos >= start) & (pos < src.kv_len[0])
                 & (pos < self.max_len)).reshape(n, ps)
        slot = jnp.minimum(jnp.arange(n), self.pages_per_row - 1)
        page = jnp.where(valid.any(axis=1), table_row[slot], 0)
        return page, valid.reshape(-1)

    def update(self, layer: int, k_new, v_new, pos) -> "PagedKVCache":
        """Write ``k_new``/``v_new`` ([batch, s, heads, head_dim]) into
        ``layer`` at per-row start position ``pos`` through the page
        table. Decode-path contract: a live row's ``pos`` (its
        ``kv_len``) is >= 1 (it holds at least its prompt), so a row
        writing at position 0 is an idle engine lane and lands on the
        null page. Does NOT advance ``kv_len`` (same contract as the
        dense cache: the model advances it once per forward)."""
        k_new, v_new = _raw(k_new), _raw(v_new)
        page, off = self._token_dest(pos, *k_new.shape[:2])
        kernel = _paged_write.supports(self.k.shape, self.k.dtype,
                                       k_new.shape[1])
        _monitor.record_kv_write_path(kernel=kernel)
        if kernel:
            with jax.named_scope("paged_kv_write"):
                k, v = _paged_write.paged_kv_write(
                    self.k, self.v, layer, page, off,
                    _to_pool_width(k_new, self.k),
                    _to_pool_width(v_new, self.v))
        else:
            k = _scatter_tokens(self.k, layer, page, off, k_new)
            v = _scatter_tokens(self.v, layer, page, off, v_new)
        return PagedKVCache(k, v, self.page_table, self.kv_len)

    def install_row(self, src: KVCache, slot, table_row,
                    start) -> "PagedKVCache":
        """Slot admission: scatter the batch-1 dense prefill cache
        ``src`` into the pool pages named by ``table_row``
        ([pages_per_row] int32), install the table row and ``kv_len``
        at ``slot``. Positions below ``start`` are covered by shared
        prefix pages and are NOT written (the whole point); positions
        at/past ``src.kv_len[0]`` route to the null page. ``slot``,
        ``table_row`` and ``start`` are traced data — ONE compiled
        program serves every slot and every allocation layout."""
        slot = jnp.asarray(_raw(slot), jnp.int32)
        table_row = jnp.asarray(_raw(table_row), jnp.int32)
        spanned = self.install_span(src, table_row, start)
        return PagedKVCache(
            spanned.k, spanned.v,
            self.page_table.at[slot].set(table_row),
            self.kv_len.at[slot].set(src.kv_len[0]))

    def install_span(self, src: KVCache, table_row,
                     start) -> "PagedKVCache":
        """Chunked-prefill incremental install: scatter positions
        ``[start, src.kv_len[0])`` of the batch-1 dense chunk cache
        ``src`` into the pool pages named by ``table_row`` WITHOUT
        installing the table row or ``kv_len`` — the slot stays parked
        (kv_len 0, null table) so decode steps keep routing its lane's
        writes to the null page until the final chunk's admission
        installs the pointers atomically. The same program runs after
        every non-final chunk; the admission-time :meth:`install_row`
        then writes only the final span (``start`` = last chunk
        boundary)."""
        table_row = jnp.asarray(_raw(table_row), jnp.int32)
        start = jnp.asarray(_raw(start), jnp.int32)
        page, valid = self._span_dest(src, table_row, start)
        return PagedKVCache(_install_pages(self.k, src.k, page, valid),
                            _install_pages(self.v, src.v, page, valid),
                            self.page_table, self.kv_len)

    def positions(self, s: int):
        """Absolute positions of ``s`` appended tokens per row — the
        decode position-embedding offsets (dense-cache contract)."""
        return self.kv_len[:, None] + \
            jnp.arange(s, dtype=jnp.int32)[None, :]

    # -------------------------------------------------------- slot reuse
    def reset_rows(self, rows) -> "PagedKVCache":
        """Free rows for reuse: zero ``kv_len`` AND null the page-table
        row (one row index, an int array, or a [batch] bool mask). The
        HOST allocator owns returning the pages themselves to the free
        list — this program only severs the row's pointers so a stale
        lane can never write through them once the pages are
        re-owned."""
        rows = jnp.asarray(_raw(rows))
        if rows.dtype == jnp.bool_:
            kv_len = jnp.where(rows, 0, self.kv_len)
            table = jnp.where(rows[:, None], 0, self.page_table)
        else:
            kv_len = self.kv_len.at[rows].set(0)
            table = self.page_table.at[rows].set(0)
        return PagedKVCache(self.k, self.v, table, kv_len)

    def with_kv_len(self, kv_len) -> "PagedKVCache":
        kv_len = jnp.asarray(_raw(kv_len), jnp.int32)
        if kv_len.ndim == 0:
            kv_len = jnp.broadcast_to(kv_len, (self.batch,))
        return PagedKVCache(self.k, self.v, self.page_table, kv_len)

    # --------------------------------------------------------- telemetry
    def occupancy(self) -> float:
        """Host-side fraction of the LOGICAL per-row capacity in use
        (max over rows) — the gen.cache_occupancy gauge; page-level
        occupancy is the allocator's (host-only) page_occupancy."""
        top = np.max(np.asarray(self.kv_len))  # lint: host-sync-ok (tiny read)
        return float(top) / self.max_len  # lint: host-sync-ok (host scalar)

    def __repr__(self):
        return (f"PagedKVCache(layers={self.num_layers}, "
                f"batch={self.batch}, pages={self.n_pages}x"
                f"{self.page_size}, per_row={self.pages_per_row}, "
                f"dtype={self.k.dtype})")


@jax.tree_util.register_pytree_node_class
class QuantPagedKVCache(PagedKVCache):
    """Int8 page pool: K/V pages stored int8 with per-(slot, head) bf16
    scales in sidecar pools ``k_scale``/``v_scale``
    ([layers, n_pages, heads, page_size]) plus the scalar ``clips``
    saturation counter. The scales live IN the page (one row per
    position), so everything the allocator does at page granularity —
    shared-prefix referencing, COW privatization, LRU reclaim — carries
    the scales with the values for free: a referenced shared page
    dequantizes identically for every sharer, and a COW private copy
    rewrites values + scales together at install."""

    __slots__ = ("k_scale", "v_scale", "clips")

    def __init__(self, k, v, page_table, kv_len, k_scale, v_scale,
                 clips):
        super().__init__(k, v, page_table, kv_len)
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.clips = clips

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.k, self.v, self.page_table, self.kv_len,
                self.k_scale, self.v_scale, self.clips), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def cache_dtype(self):
        return "int8"

    # ------------------------------------------------------------ update
    def update(self, layer: int, k_new, v_new, pos) -> "QuantPagedKVCache":
        """Quantize the fresh k/v per (token, head) and write int8
        values + bf16 scales through the page table — same null-page
        routing for idle/out-of-table positions as the wide pool."""
        k_new, v_new = _raw(k_new), _raw(v_new)
        page, off = self._token_dest(pos, *k_new.shape[:2])
        _monitor.record_kv_write_path(kernel=False)
        kq, ks, kc = quantize_kv(k_new)
        vq, vs, vc = quantize_kv(v_new)

        def write(buf, new):
            return _scatter_tokens(buf, layer, page, off, new)

        return QuantPagedKVCache(
            write(self.k, kq), write(self.v, vq), self.page_table,
            self.kv_len, write(self.k_scale, ks), write(self.v_scale, vs),
            self.clips + kc + vc)

    def install_row(self, src, slot, table_row,
                    start) -> "QuantPagedKVCache":
        """Slot admission from a batch-1 :class:`QuantKVCache` prefill
        row: int8 values AND scales scatter verbatim through the table
        (no requantization — the installed pages decode bitwise-equal
        to the dense row), positions below ``start`` stay covered by
        the shared prefix pages, masked positions route to null."""
        slot = jnp.asarray(_raw(slot), jnp.int32)
        table_row = jnp.asarray(_raw(table_row), jnp.int32)
        spanned = self.install_span(src, table_row, start)
        return QuantPagedKVCache(
            spanned.k, spanned.v,
            self.page_table.at[slot].set(table_row),
            self.kv_len.at[slot].set(src.kv_len[0]),
            spanned.k_scale, spanned.v_scale, self.clips + src.clips)

    def install_span(self, src, table_row,
                     start) -> "QuantPagedKVCache":
        """Chunked-prefill incremental install from a batch-1
        :class:`QuantKVCache` chunk row: int8 values + scales scatter
        for ``[start, src.kv_len[0])`` only, table row and ``kv_len``
        untouched (see the wide-pool docstring). ``clips`` is NOT
        accumulated here — the admission-time ``install_row`` adds the
        source cache's counter once; adding it per span would
        multiply-count every earlier chunk's clips."""
        table_row = jnp.asarray(_raw(table_row), jnp.int32)
        start = jnp.asarray(_raw(start), jnp.int32)
        page, valid = self._span_dest(src, table_row, start)

        def write(buf, rows):
            return _install_pages(buf, rows, page, valid)

        return QuantPagedKVCache(
            write(self.k, src.k), write(self.v, src.v),
            self.page_table, self.kv_len,
            write(self.k_scale, src.k_scale),
            write(self.v_scale, src.v_scale), self.clips)

    # -------------------------------------------------------- slot reuse
    def reset_rows(self, rows) -> "QuantPagedKVCache":
        base = PagedKVCache.reset_rows(self, rows)
        return QuantPagedKVCache(self.k, self.v, base.page_table,
                                 base.kv_len, self.k_scale, self.v_scale,
                                 self.clips)

    def with_kv_len(self, kv_len) -> "QuantPagedKVCache":
        kv_len = jnp.asarray(_raw(kv_len), jnp.int32)
        if kv_len.ndim == 0:
            kv_len = jnp.broadcast_to(kv_len, (self.batch,))
        return QuantPagedKVCache(self.k, self.v, self.page_table, kv_len,
                                 self.k_scale, self.v_scale, self.clips)

    def __repr__(self):
        return (f"QuantPagedKVCache(layers={self.num_layers}, "
                f"batch={self.batch}, pages={self.n_pages}x"
                f"{self.page_size}, per_row={self.pages_per_row}, "
                f"dtype=int8+bf16-scales)")


class AdmissionPlan:
    """One admission's page plan (host-only): the shared prefix pages to
    reference, how many private pages to allocate, and whether the
    divergence point sits inside a shared page (copy-on-write)."""

    __slots__ = ("shared_pages", "shared_len", "n_private", "cow",
                 "total_pages", "keys")

    def __init__(self, shared_pages, shared_len, n_private, cow,
                 total_pages, keys):
        self.shared_pages = shared_pages    # List[int]
        self.shared_len = shared_len        # tokens covered by sharing
        self.n_private = n_private          # pages to allocate
        self.cow = cow                      # divergence inside a shared
        #                                     page -> private copy made
        self.total_pages = total_pages
        self.keys = keys                    # full-page registry keys


class PageAllocator:
    """Host-side page bookkeeping: free list, refcounts, and the
    prompt-prefix registry. Page 0 is reserved (the null page) and is
    never allocated. All state is host ints — no device arrays, no
    syncs; the engine calls ``plan``/``commit`` at admission,
    ``register`` after install, and ``free_row`` at completion or
    eviction. ``assert_conserved`` is the drain-time invariant: every
    page is exactly one of {null, free, referenced, cached}."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the "
                             "reserved null page)")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # leaf lock: the scheduler mutates under the engine's pump
        # lock while the telemetry HTTP thread reads free_pages() for
        # /readyz — an unguarded registry iteration there would raise
        # mid-scrape exactly when the router signal matters
        self._lock = threading.Lock()
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}          # page -> live row refs
        # prefix registry: full-page key -> page id (insertion order is
        # the LRU order; re-registration moves to the back)
        self._prefix: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        self._page_key: Dict[int, bytes] = {}   # page -> its registry key
        self.stats = dict(pages_allocated=0, pages_freed=0,
                          prefix_hits=0, shared_pages=0, cow_copies=0,
                          reclaimed=0)
        # bumped on every state mutation: the engine caches a blocked
        # queue head's failed plan against this, so a saturated pool is
        # re-planned only when something actually changed (a free, a
        # reclaim, a registration) instead of on every pump iteration
        self.version = 0

    # ---------------------------------------------------------- hashing
    def _page_keys(self, ids: np.ndarray) -> List[bytes]:
        """Chained per-page digests of the prompt's FULL pages: key i
        commits to every token in pages 0..i, so a match at page i
        implies the whole prefix matches."""
        keys, h = [], hashlib.blake2b(digest_size=16)
        ps = self.page_size
        for i in range(len(ids) // ps):
            h.update(np.ascontiguousarray(
                ids[i * ps:(i + 1) * ps]).tobytes())
            keys.append(h.digest())
        return keys

    # ------------------------------------------------------- accounting
    def free_pages(self) -> int:
        """Pages allocatable right now: the free list plus cached
        (registered, refcount-0) pages the reclaimer may take.
        Thread-safe: the telemetry thread calls this mid-traffic."""
        with self._lock:
            return len(self._free) + sum(
                1 for p in self._prefix.values() if not self._ref.get(p))

    def used_pages(self) -> int:
        return len(self._ref)

    def page_occupancy(self) -> float:
        """Referenced pages / allocatable universe (excludes null)."""
        return len(self._ref) / max(1, self.n_pages - 1)

    # -------------------------------------------------------- admission
    def plan(self, ids: np.ndarray, extra_tokens: int) -> AdmissionPlan:
        """Plan one admission: prompt ``ids`` plus ``extra_tokens`` of
        decode budget (incl. any speculative overhang). Pure read —
        commits nothing."""
        ids = np.asarray(ids, np.int32).reshape(-1)  # lint: host-sync-ok (host token ids)
        ps = self.page_size
        plen = int(ids.size)
        total = -(-(plen + int(extra_tokens)) // ps)
        keys = self._page_keys(ids)
        shared: List[int] = []
        with self._lock:
            for key in keys:
                page = self._prefix.get(key)
                if page is None:
                    break
                shared.append(page)
        shared_len = len(shared) * ps
        # copy-on-write: the prompt diverges INSIDE a page whose prefix
        # is shared (its full pages all matched and a partial tail
        # remains) — the install privatizes that page's content
        cow = bool(shared) and shared_len == (plen // ps) * ps \
            and plen % ps != 0
        return AdmissionPlan(shared, shared_len, total - len(shared),
                             cow, total, keys)

    def commit(self, plan: AdmissionPlan) -> Optional[List[int]]:
        """Acquire the plan's pages: reference the shared prefix pages
        and allocate the private ones (reclaiming cached prefix pages
        LRU if the free list runs dry). Returns the row's full page
        list (shared + private, position order), or None when the pool
        cannot cover it — the caller leaves the request queued."""
        with self._lock:
            if plan.n_private > len(self._free):
                self._reclaim(plan.n_private - len(self._free),
                              protect=set(plan.shared_pages))
            if plan.n_private > len(self._free):
                return None
            private = [self._free.pop() for _ in range(plan.n_private)]
            for p in private:
                self._ref[p] = 1
            for p in plan.shared_pages:
                self._ref[p] = self._ref.get(p, 0) + 1
            self.version += 1
            self.stats["pages_allocated"] += len(private)
            if plan.shared_pages:
                self.stats["prefix_hits"] += 1
                self.stats["shared_pages"] += len(plan.shared_pages)
            if plan.cow:
                self.stats["cow_copies"] += 1
            return plan.shared_pages + private

    def register(self, plan: AdmissionPlan, pages: List[int]):
        """Register the admitted prompt's FULL pages for future prefix
        hits (key i -> pages[i]). Safe because full prompt pages are
        never written after install: decode appends at the row's
        kv_len, past the last full prompt page's content. Re-registering
        a shared page refreshes its LRU position."""
        with self._lock:
            for i, key in enumerate(plan.keys):
                old = self._prefix.pop(key, None)
                if old is not None and old != pages[i]:
                    # the key was re-installed onto a different page
                    # while the old one still exists (it was referenced
                    # when this admission planned around it): drop the
                    # old binding
                    self._page_key.pop(old, None)
                    self._maybe_release(old)
                self._prefix[key] = pages[i]
                self._page_key[pages[i]] = key
            if plan.keys:
                self.version += 1

    def free_row(self, pages: List[int]):
        """Release one row's page references (completion/eviction).
        Unreferenced unregistered pages return to the free list;
        unreferenced REGISTERED pages stay cached for future prefix
        hits until reclaimed."""
        with self._lock:
            for p in pages:
                n = self._ref.get(p, 0) - 1
                if n > 0:
                    self._ref[p] = n
                else:
                    self._ref.pop(p, None)
                    self._maybe_release(p)
            self.version += 1

    def _maybe_release(self, page: int):  # lint: lock-discipline-ok (caller holds self._lock)
        if page in self._ref or page in self._page_key:
            return
        self._free.append(page)
        self.stats["pages_freed"] += 1

    def _reclaim(self, need: int, protect=frozenset()):  # lint: lock-discipline-ok (caller holds self._lock)
        """Evict cached (refcount-0, registered) prefix pages LRU-first
        until ``need`` pages were freed or nothing reclaimable is
        left. Caller holds self._lock."""
        for key in list(self._prefix):
            if need <= 0:
                break
            page = self._prefix[key]
            if self._ref.get(page) or page in protect:
                continue
            del self._prefix[key]
            del self._page_key[page]
            self._free.append(page)
            self.version += 1
            self.stats["pages_freed"] += 1
            self.stats["reclaimed"] += 1
            need -= 1

    def drop_registry(self):
        """Forget every cached prefix (refcount-0 registered pages go
        back to the free list) — test/diagnostic hook."""
        with self._lock:
            self._reclaim(len(self._prefix))
            # still-referenced registered pages lose their registry entry
            for key in list(self._prefix):
                page = self._prefix.pop(key)
                self._page_key.pop(page, None)
            self.version += 1

    # ------------------------------------------------------ invariants
    def assert_conserved(self):
        """Every page is exactly one of {null, free, referenced,
        cached}: no leaks, no double frees. The chaos drain gate."""
        with self._lock:
            return self._assert_conserved_locked()

    def _assert_conserved_locked(self):
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("double-freed page(s): free list has "
                                 "duplicates")
        refd = set(self._ref)
        cached = {p for p in self._page_key if p not in refd}
        if free & refd or free & cached:
            raise AssertionError(
                f"page in two states: free∩ref={sorted(free & refd)} "
                f"free∩cached={sorted(free & cached)}")
        if 0 in free or 0 in refd or 0 in cached:
            raise AssertionError("reserved null page 0 was allocated")
        total = 1 + len(free) + len(refd) + len(cached)
        if total != self.n_pages:
            raise AssertionError(
                f"page leak: null+free({len(free)})+referenced"
                f"({len(refd)})+cached({len(cached)}) = {total} != "
                f"pool {self.n_pages}")

    def __repr__(self):
        return (f"PageAllocator(pages={self.n_pages}x{self.page_size}, "
                f"free={len(self._free)}, used={len(self._ref)}, "
                f"cached={len(self._prefix)})")

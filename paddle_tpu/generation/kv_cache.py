"""Ring KV cache for incremental decoding.

One donated on-device pytree holds every layer's cached keys/values:

    k, v:   [num_layers, batch, max_len, num_heads, head_dim]
    kv_len: [batch] int32 — valid entries per row (ragged batches)

``update(layer, k, v, pos)`` is pure-functional (returns a new KVCache
whose buffers alias the old ones under XLA donation), so the SAME code
path jit-compiles for prefill (write the whole padded prompt at pos 0)
and decode (write 1..8 new rows at each row's ``kv_len``). Write
positions wrap modulo ``max_len`` (ring semantics); ``generate()``
validates lengths up front so a live cache never actually wraps — the
wrap exists so an out-of-contract write corrupts the oldest entries
instead of faulting.

Sharding: ``partition_spec()`` places batch on the (dp, sharding) mesh
axes and heads on mp — the same layout the models' qkv activations
carry under ``DistributedTrainStep`` — so hybrid-mesh models decode
without resharding. ``shard(mesh)`` trims the spec to the axes the mesh
actually has.

Quantized mode (``cache_dtype="int8"``, ROADMAP item 4): at long
context decode is bandwidth-bound on STREAMING the cache, so
:class:`QuantKVCache` stores K/V as int8 with a bfloat16 scale per
(position, head) in small sidecar arrays — half the HBM bytes per
decode step (and double the rows a fixed pool holds, compounding with
the paged cache). ``update`` quantizes IN-TRACE at write time (absmax
over head_dim per appended token), and the decode kernels dequantize
in-register: the K scale folds into the score-tile columns and the V
scale into the softmax weights, so a wide cache is never materialized
anywhere. A tiny ``clips`` counter rides the pytree recording values
that saturated the int8 range (the bf16 scale rounding can clip a
token's absmax element by <=0.4%) — drained into
``gen.cache.quant.scale_clips``.

Reference analog: the fused-multi-transformer decode ops' CacheKV
tensors (paddle/fluid/operators/fused/fused_multi_transformer_op.cu);
here the cache is a plain pytree the compiled step updates in place via
buffer donation.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

#: cache dtypes ``KVCache.create(cache_dtype=)`` accepts (None = the
#: activation dtype, the full-width mode)
CACHE_DTYPES = (None, "int8")


def _raw(x):
    from ..core.tensor import Tensor
    return x._data if isinstance(x, Tensor) else x


def validate_cache_dtype(value):
    """Reject anything outside CACHE_DTYPES with the one shared error
    (config knobs, cache constructors, and the resolver all call this
    — one rule, one message)."""
    if value not in CACHE_DTYPES:
        raise ValueError(
            f"kv_cache_dtype {value!r}: one of "
            f"{[d for d in CACHE_DTYPES if d]} or None (full width)")
    return value


def resolve_cache_dtype(explicit=None):
    """The effective KV-cache dtype: an explicit value wins (and is
    validated — a typo'd config raises, never silently serves wide),
    else ``PADDLE_KV_CACHE_DTYPE``; garbage in the env is recorded via
    ``record_swallowed`` and falls back to full width (same contract as
    PADDLE_KV_PAGE_SIZE)."""
    if explicit is not None:
        return validate_cache_dtype(explicit)
    env = os.environ.get("PADDLE_KV_CACHE_DTYPE", "").strip().lower()
    if not env or env in ("auto", "none", "off", "wide", "float"):
        return None
    if env in CACHE_DTYPES:
        return env
    from ..core import monitor
    monitor.record_swallowed(
        "generation.kv_cache_dtype",
        ValueError(f"PADDLE_KV_CACHE_DTYPE={env!r}"))
    return None


def quantize_kv(x):
    """Quantize fresh K or V values ``[..., heads, head_dim]`` to int8
    with one bfloat16 scale per (..., head): ``scale = absmax/127``
    (bf16-rounded — half the sidecar HBM of fp32, and the rounding
    error is an order below the int8 step), ``q = round(x / scale)``
    clipped to the int8 range. Returns ``(q int8, scale bf16, clips)``
    where ``clips`` counts values that saturated past +-127 BEFORE the
    clip — structurally 0 under round-to-nearest absmax scales (the
    worst-case ratio is 127 * (1 + 2^-9) < 127.5), so a nonzero count
    is the alarm that a future scale scheme (calibrated, EMA,
    coarser-grained) actually saturates."""
    xf = _raw(x).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)               # [..., heads]
    scale = (jnp.maximum(absmax, 1e-6) / 127.0).astype(jnp.bfloat16)
    q = jnp.round(xf / scale.astype(jnp.float32)[..., None])
    clips = jnp.sum((jnp.abs(q) > 127.0).astype(jnp.int32))
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return q, scale, clips


def _axis_trimmer(mesh):
    """Trim partition-spec axes to the names ``mesh`` actually has."""
    names = set(mesh.axis_names)

    def trim(axes):
        if isinstance(axes, tuple):
            kept = tuple(a for a in axes if a in names)
            return kept if kept else None
        return axes if axes in names else None

    return trim


@jax.tree_util.register_pytree_node_class
class KVCache:
    """Per-layer K/V ring cache with per-row valid lengths."""

    __slots__ = ("k", "v", "kv_len")

    def __init__(self, k, v, kv_len):
        self.k = k
        self.v = v
        self.kv_len = kv_len

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.k, self.v, self.kv_len), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ------------------------------------------------------------- shape
    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def dtype(self):
        return self.k.dtype

    @property
    def cache_dtype(self):
        """The declared low-bit storage mode (None = full width)."""
        return None

    # ---------------------------------------------------------- creation
    @classmethod
    def create(cls, num_layers: int, batch: int, max_len: int,
               num_heads: int, head_dim: int, dtype=jnp.float32,
               mesh=None, cache_dtype=None) -> "KVCache":
        shape = (num_layers, batch, max_len, num_heads, head_dim)
        if validate_cache_dtype(cache_dtype) is not None:
            sshape = (num_layers, batch, max_len, num_heads)
            cache = QuantKVCache(
                jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros((batch,), jnp.int32),
                jnp.zeros(sshape, jnp.bfloat16),
                jnp.zeros(sshape, jnp.bfloat16),
                jnp.zeros((), jnp.int32))
        else:
            cache = cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                        jnp.zeros((batch,), jnp.int32))
        return cache.shard(mesh) if mesh is not None else cache

    @staticmethod
    def partition_spec() -> P:
        """[layers, batch, max_len, heads, head_dim]: batch over
        (dp, sharding), heads over mp — the models' qkv layout."""
        return P(None, ("dp", "sharding"), None, "mp", None)

    def shard(self, mesh) -> "KVCache":
        """Place the cache on ``mesh`` (spec trimmed to the axes the
        mesh has). Works both eagerly (device_put) and inside a trace
        (sharding constraint)."""
        trim = _axis_trimmer(mesh)
        spec = P(*(trim(ax) for ax in self.partition_spec()))
        kv_sh = NamedSharding(mesh, spec)
        len_sh = NamedSharding(mesh, P(trim(("dp", "sharding"))))
        place = jax.lax.with_sharding_constraint \
            if isinstance(self.k, jax.core.Tracer) else jax.device_put
        return KVCache(place(self.k, kv_sh), place(self.v, kv_sh),
                       place(self.kv_len, len_sh))

    # ------------------------------------------------------------ update
    def update(self, layer: int, k_new, v_new, pos) -> "KVCache":
        """Write ``k_new``/``v_new`` ([batch, s, heads, head_dim]) into
        ``layer`` at per-row start position ``pos`` ([batch] int32 or a
        scalar), wrapping modulo max_len. Does NOT advance ``kv_len`` —
        every layer of one forward writes at the same positions; the
        model advances the length once via ``with_kv_len``."""
        k_new, v_new = _raw(k_new), _raw(v_new)
        pos = jnp.asarray(_raw(pos), jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (k_new.shape[0],))
        steps = jnp.arange(k_new.shape[1], dtype=jnp.int32)

        def write(buf, new, p):  # [T, H, D], [S, H, D], scalar
            # scatter, not dynamic_update_slice: each target slot wraps
            # modulo max_len independently (true ring semantics; a
            # slice write would CLAMP at the end instead)
            idx = (p + steps) % buf.shape[0]
            return buf.at[idx].set(new.astype(buf.dtype))

        k_l = jax.vmap(write)(self.k[layer], k_new, pos)
        v_l = jax.vmap(write)(self.v[layer], v_new, pos)
        return KVCache(self.k.at[layer].set(k_l),
                       self.v.at[layer].set(v_l), self.kv_len)

    def positions(self, s: int):
        """Absolute positions of ``s`` appended tokens per row
        ([batch, s] int32: ``kv_len[r] .. kv_len[r]+s-1``) — the decode
        position-embedding offsets."""
        return self.kv_len[:, None] + \
            jnp.arange(s, dtype=jnp.int32)[None, :]

    # -------------------------------------------------------- slot reuse
    def reset_rows(self, rows) -> "KVCache":
        """Free batch rows for reuse: zero ``kv_len`` at ``rows`` (one
        row index, an int array of rows, or a [batch] bool mask)
        without touching the K/V buffers or the pytree structure — the
        serving scheduler calls this (jit-compiled, cache donated) when
        a slot's request terminates, so slot turnover never rebuilds or
        reallocates the cache. Stale K/V beyond a reset row's kv_len is
        invisible (attention masks by kv_len) and the next
        prefill-into-slot overwrites it; after a reset the ring write
        position wraps back to 0 for that row."""
        rows = jnp.asarray(_raw(rows))
        if rows.dtype == jnp.bool_:
            kv_len = jnp.where(rows, 0, self.kv_len)
        else:
            kv_len = self.kv_len.at[rows].set(0)
        return KVCache(self.k, self.v, kv_len)

    def copy_row_from(self, src: "KVCache", src_row, dst_row) -> "KVCache":
        """Slot admission: overwrite row ``dst_row`` of this cache with
        row ``src_row`` of ``src`` — K, V, and kv_len — leaving every
        other row untouched. ``src`` must share layers/max_len/heads/
        head_dim (typically a batch-1 prefill cache being installed
        into a freed slot of the shared decode cache). Row indices may
        be traced scalars, so ONE compiled program serves every slot."""
        src_row = jnp.asarray(_raw(src_row), jnp.int32)
        dst_row = jnp.asarray(_raw(dst_row), jnp.int32)
        return KVCache(
            self.k.at[:, dst_row].set(src.k[:, src_row].astype(self.k.dtype)),
            self.v.at[:, dst_row].set(src.v[:, src_row].astype(self.v.dtype)),
            self.kv_len.at[dst_row].set(src.kv_len[src_row]))

    def install_row(self, src: "KVCache", slot) -> "KVCache":
        """Slot admission as the serving engine's admit program spells
        it for every cache: the batch-1 prefill cache ``src`` becomes
        row ``slot``. A page pool's ``install_row`` takes, after these,
        where the row goes (its page table and first written position);
        a dense row needs neither."""
        return self.copy_row_from(src, 0, slot)

    def with_kv_len(self, kv_len) -> "KVCache":
        kv_len = jnp.asarray(_raw(kv_len), jnp.int32)
        if kv_len.ndim == 0:
            kv_len = jnp.broadcast_to(kv_len, (self.batch,))
        return KVCache(self.k, self.v, kv_len)

    def paged(self, n_pages: int, page_size: int, pages_per_row: int):
        """The avals of the page pool that serves these dense rows
        (``self`` may itself be avals: only shapes and dtypes are read):
        layers, heads, head_dim and dtype stay, the ``batch`` rows of
        ``max_len`` become ``n_pages`` pages of ``page_size`` and a
        ``[batch, pages_per_row]`` table. The serving engine builds its
        cache from what the model's prefill returns through this, so a
        cache kind states its own paged form."""
        from .paged_cache import PagedKVCache, pool_head_dim
        sds = jax.ShapeDtypeStruct
        L, B, _, H, D = self.k.shape
        pool = (L, n_pages, H, page_size, pool_head_dim(D))
        return PagedKVCache(sds(pool, self.k.dtype), sds(pool, self.v.dtype),
                            sds((B, pages_per_row), jnp.int32),
                            sds((B,), jnp.int32))

    # --------------------------------------------------------- telemetry
    def occupancy(self) -> float:
        """Host-side fraction of the cache in use (max over rows) — the
        gen.cache_occupancy gauge. Syncs kv_len (a [batch] int32 — a
        few bytes) to host."""
        import numpy as np
        top = np.max(np.asarray(self.kv_len))  # lint: host-sync-ok (tiny read)
        return float(top) / self.max_len  # lint: host-sync-ok (host scalar)

    def __repr__(self):
        return (f"KVCache(layers={self.num_layers}, batch={self.batch}, "
                f"max_len={self.max_len}, dtype={self.k.dtype})")


@jax.tree_util.register_pytree_node_class
class QuantKVCache(KVCache):
    """Int8 ring cache: K/V stored int8 with per-(position, head) bf16
    scales in sidecar arrays ``k_scale``/``v_scale``
    ([layers, batch, max_len, heads]) plus a scalar ``clips`` int32
    counting int8 saturations. Same protocol as :class:`KVCache` —
    ``update`` quantizes in-trace at write time, and the decode kernels
    read the scale rows beside ``kv_len`` to dequantize in-register
    (``kernels.flash_attention_decode(k_scale=, v_scale=)``). Scales
    are per written position, so an append-only update never needs to
    requantize earlier entries (a coarser running-absmax scale would),
    and a row/page copy moves values + scales verbatim — admission
    installs and COW privatizations stay bitwise."""

    __slots__ = ("k_scale", "v_scale", "clips")

    def __init__(self, k, v, kv_len, k_scale, v_scale, clips):
        super().__init__(k, v, kv_len)
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.clips = clips

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.k, self.v, self.kv_len, self.k_scale, self.v_scale,
                self.clips), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def cache_dtype(self):
        return "int8"

    def shard(self, mesh) -> "QuantKVCache":
        trim = _axis_trimmer(mesh)
        spec = P(*(trim(ax) for ax in self.partition_spec()))
        kv_sh = NamedSharding(mesh, spec)
        # scales: [layers, batch, max_len, heads] — same layout minus
        # the head_dim axis
        sc_sh = NamedSharding(mesh, P(*(trim(ax) for ax in
                                        self.partition_spec()[:-1])))
        len_sh = NamedSharding(mesh, P(trim(("dp", "sharding"))))
        rep_sh = NamedSharding(mesh, P())
        place = jax.lax.with_sharding_constraint \
            if isinstance(self.k, jax.core.Tracer) else jax.device_put
        return QuantKVCache(
            place(self.k, kv_sh), place(self.v, kv_sh),
            place(self.kv_len, len_sh), place(self.k_scale, sc_sh),
            place(self.v_scale, sc_sh), place(self.clips, rep_sh))

    # ------------------------------------------------------------ update
    def update(self, layer: int, k_new, v_new, pos) -> "QuantKVCache":
        """Quantize the fresh k/v (absmax per appended token x head) and
        ring-write int8 values + bf16 scales at ``pos``; saturated
        values bump ``clips``. Same contract as the wide cache."""
        k_new, v_new = _raw(k_new), _raw(v_new)
        pos = jnp.asarray(_raw(pos), jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (k_new.shape[0],))
        steps = jnp.arange(k_new.shape[1], dtype=jnp.int32)
        kq, ks, kc = quantize_kv(k_new)
        vq, vs, vc = quantize_kv(v_new)

        def write(buf, new, p):  # [T, ...], [S, ...], scalar
            idx = (p + steps) % buf.shape[0]
            return buf.at[idx].set(new.astype(buf.dtype))

        k_l = jax.vmap(write)(self.k[layer], kq, pos)
        v_l = jax.vmap(write)(self.v[layer], vq, pos)
        ks_l = jax.vmap(write)(self.k_scale[layer], ks, pos)
        vs_l = jax.vmap(write)(self.v_scale[layer], vs, pos)
        return QuantKVCache(
            self.k.at[layer].set(k_l), self.v.at[layer].set(v_l),
            self.kv_len, self.k_scale.at[layer].set(ks_l),
            self.v_scale.at[layer].set(vs_l), self.clips + kc + vc)

    # -------------------------------------------------------- slot reuse
    def reset_rows(self, rows) -> "QuantKVCache":
        base = KVCache.reset_rows(self, rows)
        return QuantKVCache(self.k, self.v, base.kv_len, self.k_scale,
                            self.v_scale, self.clips)

    def copy_row_from(self, src: "QuantKVCache", src_row,
                      dst_row) -> "QuantKVCache":
        """Slot admission: int8 values AND their scales copy verbatim —
        no requantization, so an installed row decodes bitwise-equal to
        its batch-1 prefill. ``src.clips`` (the prefill's saturation
        count) folds into this cache's counter."""
        src_row = jnp.asarray(_raw(src_row), jnp.int32)
        dst_row = jnp.asarray(_raw(dst_row), jnp.int32)
        return QuantKVCache(
            self.k.at[:, dst_row].set(src.k[:, src_row]),
            self.v.at[:, dst_row].set(src.v[:, src_row]),
            self.kv_len.at[dst_row].set(src.kv_len[src_row]),
            self.k_scale.at[:, dst_row].set(src.k_scale[:, src_row]),
            self.v_scale.at[:, dst_row].set(src.v_scale[:, src_row]),
            self.clips + src.clips)

    def with_kv_len(self, kv_len) -> "QuantKVCache":
        kv_len = jnp.asarray(_raw(kv_len), jnp.int32)
        if kv_len.ndim == 0:
            kv_len = jnp.broadcast_to(kv_len, (self.batch,))
        return QuantKVCache(self.k, self.v, kv_len, self.k_scale,
                            self.v_scale, self.clips)

    def paged(self, n_pages: int, page_size: int, pages_per_row: int):
        """Value pages + their bf16 scale pages (the scales live IN the
        page, so prefix sharing / COW / reclaim carry them for free) +
        the saturation counter."""
        from .paged_cache import QuantPagedKVCache
        sds = jax.ShapeDtypeStruct
        wide = KVCache.paged(self, n_pages, page_size, pages_per_row)
        scales = sds(wide.k.shape[:-1], jnp.bfloat16)
        return QuantPagedKVCache(wide.k, wide.v, wide.page_table,
                                 wide.kv_len, scales, scales,
                                 sds((), jnp.int32))

    def __repr__(self):
        return (f"QuantKVCache(layers={self.num_layers}, "
                f"batch={self.batch}, max_len={self.max_len}, "
                f"dtype=int8+bf16-scales)")

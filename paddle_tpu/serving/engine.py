"""Continuous-batching serving engine: slot-scheduled decode over ONE
shared, donated KV cache.

The reference ships serving as a whole layer (paddle/fluid/inference,
~90k LoC — PAPER.md §1); ours is a slot scheduler over the AOT
(prefill, decode) machinery PR 6 built:

- **decode never drains and never retraces.** The decode step always
  runs at the fixed batch of ``max_batch`` slots against the shared
  ring KVCache. A finished row (eos or budget) is masked by its
  ``finished`` lane, its tokens stop advancing, and its ``kv_len`` is
  pinned to 0 in-trace — the slot is freed IN PLACE, no reshape, no
  re-trace, no rebuild of the cache pytree.
- **admission = prefill into a slot.** A queued request is prefilled
  alone (batch 1) at its prompt's shape bucket (the
  ``Config.enable_generation`` bucket set), then a jitted admit program
  installs the row cache into the freed slot (the cache's
  ``install_row``) and resets that slot's token/finished/step/budget
  lanes. One admit program serves every slot — the slot index is data,
  not shape.
- **paged KV cache + shared-prefix reuse**
  (``enable_serving(paged=True)``): the dense ring is replaced by a
  pool of fixed-size pages addressed through per-slot int32 page
  tables (``generation.PagedKVCache``). Admission plans pages on the
  host (prompt + the request's OWN budget), hashes the prompt's full
  pages against the prefix registry so identical system prompts are
  stored once and reference-counted (copy-on-write at divergence), and
  blocks on FREE PAGES as well as free slots — ``health()`` tells the
  two pressures apart (``no_free_pages`` vs ``no_free_slots``).
  Outputs stay bitwise-equal to the dense cache; page conservation is
  asserted at drain in the chaos tier.
- **every program is compiled at warmup.** ``warmup()`` AOT-lowers one
  prefill executable per bucket plus the decode/admit/free trio; after
  it, a compile the engine is ever forced to do mid-traffic is recorded
  as ``jit.compile{cause=new_shape}`` — the steady-state no-retrace
  invariant the tier-1 gate asserts stays 0. With an executable store
  active (``executable_store=`` or the ``jit.compile_cache`` process
  default) warmup loads serialized executables a previous launch
  persisted — a rolling relaunch warm-starts with zero XLA compiles
  (``jit.compile_cache.hits`` == program count, ``misses`` == 0).
- **precision**: the engine serves the bf16/fp16 cast (and the int8
  weight-only / int8-compute hooks) through the same
  ``inference.precision.serving_params`` the Predictor audits —
  BASELINE.md measured 1.49-1.79x matmul wins at bf16.
- **speculative decoding on the slots**
  (``enable_generation(speculative="ngram")``): the decode step becomes
  a fused prompt-lookup draft + single-dispatch verify — every live
  row advances 1..k+1 tokens per dispatch, with accepted-length-aware
  ``steps``/budget/eos accounting (clamped so a row never writes past
  its budget or ring capacity), per-slot token-history lanes installed
  at admit, and on-device proposed/accepted counters drained into
  ``gen.spec.*`` at each poll. Greedy outputs stay bitwise-equal to
  sequential decode; drain/eviction semantics are unchanged (partial
  results are accepted-only).
- **block diffusion on the slots**
  (``enable_generation(block_diffusion={...})``): the step forwards each
  lane's current BLOCK of ``block_length`` positions and either unmasks
  its most confident masked positions (a denoise step) or, once none is
  masked, advances the cache past it and opens the next (a commit step)
  — which of the two is per-lane data, so one program serves every
  phase (``generation/block_diffusion.py``). A lane emits 0, 1 or
  several tokens a step, out of order inside its block: ``_steps``, the
  poll, ``Request.n_emitted``, ``stats["emitted_tokens"]``, budgets and
  page planning all count TOKENS UNMASKED, not steps. The prefill
  commits the prompt's whole blocks (block-causal) and samples nothing;
  ``Request.unmask_steps`` comes back with ``tokens``.
- **SLA observability**: the ``serve.*`` metrics family (requests by
  terminal status, queue-depth gauge, TTFT + per-token latency
  histograms, slot occupancy, cancellations) flows through
  ``core.monitor`` into the existing Perfetto export.

This module is the SCHEDULER. The device programs, their operands and
donation, and the three step modes (plain decode, n-gram speculation,
block diffusion) are ``serving/programs.py``: the engine holds one
program table and one mode object, and calls them.

Host syncs are confined to the scheduler's poll cadence (every
``poll_every`` decode steps: one read of the [batch] lanes), one small
sync per admission (the TTFT measurement point), and one read of the
result rows per poll that completed a lane — the decode hot loop itself
dispatches without waiting. **The scheduler blocks on the device only
after it has given the device the work that follows the value it
reads**: an iteration dispatches an admission (prefill, then the
admit program on the prefill's outputs, device arrays still) and its
decode step, and only then waits for the prefill's token (with several
slots free, an earlier admission's token is waited for with its own
admit program behind the wait, before the next prefill goes out: two
prefill rows are alive at most); a poll of a full engine dispatches the next decode step first and reads the lanes
as the step before it left them (the ``poll_view`` program's copies:
the step donates the lanes themselves). ``serve.sync``'s ``ahead`` says
how many programs were queued behind the one a read waited for.

Every scheduler iteration, page plan, admission, blocking read, program
dispatch and poll is a flight-recorder span (``serve.step`` >
``serve.plan`` / ``serve.admit`` / ``serve.dispatch{program}`` /
``serve.poll`` > ``serve.sync{site=...}`` / ``serve.telemetry``;
``core/flight_recorder.DECLARED_SPANS``): no span but ``serve.sync``
holds a blocking read, so an iteration's host time splits by purpose.
``serve.step`` closes with the garbage collector's time inside it
(``gc_ms``), and one that stamps no boundary for 250 ms leaves a
``serve.stall`` event (the recorder's watcher). Every request records
``serve.queue_wait`` + ``serve.prefill``. Each boundary is stamped once,
on the recorder's clock; the request's ``admitted_at`` /
``first_token_at``, the TTFT and per-token latency metrics, the goodput
charges and the per-request cost are all derived from those stamps.
"""
from __future__ import annotations

import collections
import heapq
import os
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flight_recorder, monitor
from ..core import slo as slo_mod
from ..core.tensor import Tensor
from ..generation.api import GenerationConfig, _round_up
from . import programs
from .request import (QueueFull, Request, RequestParams, RequestStatus)

__all__ = ["ServingEngine"]


def _env_int(var: str, site: str) -> Optional[int]:
    """The non-negative integer ``var`` holds, None when unset. Garbage
    must not silently enable, resize or re-shape anything: it is
    swallowed observably (``site``) and reads as unset."""
    raw = os.environ.get(var, "").strip()
    if raw.isdigit():
        return int(raw)
    if raw:
        monitor.record_swallowed(site, ValueError(f"{var}={raw!r}"))
    return None


def _host_zeros(avals):
    """Zeroed device buffers of ``avals``, built on the HOST and
    ``device_put``: ``jnp.zeros`` would compile one tiny broadcast
    program per shape — dead weight on the warm-relaunch path the
    executable store keeps otherwise XLA-free."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(np.zeros(a.shape, a.dtype)), avals)


class ServingEngine:
    """Slot-scheduled continuous batching over a live generative layer.

    ::

        cfg = (inference.Config().from_layer(model, input_spec)
               .enable_generation(max_new_tokens=64,
                                  prefill_buckets=(64, 128, 256),
                                  max_batch=8, eos_token_id=50256)
               .enable_serving(max_queue=128))
        engine = ServingEngine(cfg)
        handle = engine.submit(prompt_ids,
                               RequestParams(max_new_tokens=32))
        tokens = handle.result()          # pumps inline if no thread
        # or: engine.serve_forever(request_iter)   # blocking loop
        # or: engine.start(); ...; engine.shutdown()

    The config must name a live layer implementing the KV-cache
    protocol (``Config.from_layer``) and have ``enable_generation()``
    set; ``enable_serving()`` and the keyword arguments below tune the
    scheduler (kwargs win)."""

    def __init__(self, config, *, max_queue: Optional[int] = None,
                 poll_every: Optional[int] = None,
                 drain_timeout_s: Optional[float] = None,
                 default_deadline_s: Optional[float] = None,
                 cache_max_len: Optional[int] = None,
                 warmup: bool = True, seed: Optional[int] = None,
                 executable_store=None,
                 trace_sample: Optional[int] = None,
                 telemetry_port: Optional[int] = None,
                 paged: Optional[bool] = None,
                 kv_page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 hbm_budget=None):
        with flight_recorder.span("setup.engine_init"):
            from ..inference.precision import serving_params

            layer = getattr(config, "_layer", None)
            if layer is None:
                raise ValueError("ServingEngine needs a live layer: use "
                                 "Config.from_layer(...) (artifact-backed "
                                 "configs have no cache protocol to drive)")
            opts = getattr(config, "_generation", None)
            if opts is None:
                raise ValueError("ServingEngine reuses the generation "
                                 "serving setup: call "
                                 "Config.enable_generation() first")
            sopts = getattr(config, "_serving", None) or {}

            def _opt(kw, key, default):
                if kw is not None:
                    return kw
                v = sopts.get(key)
                return default if v is None else v

            self.max_queue = int(_opt(max_queue, "max_queue", 64))
            self.poll_every = max(1, int(_opt(poll_every, "poll_every", 4)))
            self.drain_timeout_s = float(  # lint: host-sync-ok (config coercion)
                _opt(drain_timeout_s, "drain_timeout_s", 30.0))
            self.default_deadline_s = _opt(default_deadline_s,
                                           "default_deadline_s", None)
            cache_max_len = _opt(cache_max_len, "cache_max_len", None)
            # per-request tracing: every request records serve.queue_wait +
            # serve.prefill; 1-in-N requests additionally carry a decode
            # segment per poll (and their prefill chunks) into the flight
            # recorder (and through it the Perfetto export). Default 8 keeps
            # the per-poll span cost off the steady-state p95; 0 turns the
            # sampled segments off.
            if os.environ.get("PADDLE_TRACE_SAMPLE", "").strip().lower() \
                    in ("off", "false", "no"):
                env_sample = 0
            else:
                env_sample = _env_int("PADDLE_TRACE_SAMPLE",
                                      "serving.trace_sample")
            self.trace_sample = int(_opt(
                trace_sample, "trace_sample",
                8 if env_sample is None else env_sample))

            # precision: the same serving cast/quant pass the Predictor's
            # run() path audits (int8-compute may swap modules; int4
            # weight-only packs Linear weights two-nibbles-per-byte)
            with flight_recorder.span("setup.state"):
                self._sp = serving_params(layer, config)
            layer = self._sp.layer
            layer.eval()
            self.network = layer
            self.config = config

            # low-bit KV cache (ROADMAP item 4): the serving knob wins over
            # the generation one, PADDLE_KV_CACHE_DTYPE fills the gap. The
            # dtype is baked into every program below (prefill creates the
            # quantized cache in-trace; decode dequantizes in-kernel).
            from ..generation.kv_cache import resolve_cache_dtype
            explicit_cd = sopts.get("kv_cache_dtype")
            if explicit_cd is None:
                explicit_cd = opts.get("kv_cache_dtype")
            self.cache_dtype = resolve_cache_dtype(explicit_cd)
            cache_kw = {} if self.cache_dtype is None \
                else {"cache_dtype": self.cache_dtype}

            self._cfg = GenerationConfig(
                do_sample=opts["do_sample"], temperature=opts["temperature"],
                top_k=opts["top_k"], top_p=opts["top_p"],
                eos_token_id=opts["eos_token_id"],
                pad_token_id=opts["pad_token_id"])
            self.max_new_tokens = int(opts["max_new_tokens"])
            self.max_batch = int(opts["max_batch"])
            if self.max_batch < 1:
                raise ValueError("max_batch must be >= 1")

            # speculative decoding on the slots: the per-poll decode step
            # becomes a fused ngram-draft + single-dispatch verify over the
            # live lanes — each dispatch advances every live row by 1..k+1
            # tokens. Only the model-free self-speculative drafter runs on
            # the engine (a draft model would need its own per-slot cache
            # admission path); generate()/the Predictor serve draft mode.
            from ..generation.speculative import as_spec_config
            self._spec = as_spec_config(opts.get("speculative"),
                                        opts.get("draft_model"))
            if self._spec is not None and self._spec.mode != "ngram":
                raise ValueError(
                    "ServingEngine supports speculative='ngram' (the "
                    "model-free prompt-lookup drafter); draft-model "
                    "speculation is a generate()/Predictor path for now")
            overhang = self._spec.k if self._spec is not None else 0
            # block diffusion on the slots: the step forwards each lane's
            # block and unmasks or commits (generation/block_diffusion).
            # The last block may reach block_length - 1 positions past
            # the budget: the same slack a verify window needs.
            from ..generation.block_diffusion import \
                as_block_diffusion_config
            self._bd = as_block_diffusion_config(
                opts.get("block_diffusion"))
            if self._bd is not None:
                if self._spec is not None:
                    raise ValueError(
                        "block_diffusion and speculative decoding are "
                        "two step programs: enable one")
                if opts["do_sample"] or opts["eos_token_id"] is not None:
                    raise ValueError(
                        "block_diffusion serves greedy requests to "
                        "their budget (no sampling, no eos stop)")
                overhang = self._bd.block_length

            max_pos = getattr(getattr(layer, "cfg", None),
                              "max_position_embeddings", None)
            buckets = sorted(
                int(b) for b in opts["prefill_buckets"]
                if max_pos is None
                or b + self.max_new_tokens + overhang <= int(max_pos))
            if not buckets:
                raise ValueError(
                    f"no prefill bucket in {opts['prefill_buckets']} fits "
                    f"max_position_embeddings={max_pos} with "
                    f"max_new_tokens={self.max_new_tokens}"
                    + (f" + speculative overhang {overhang}" if overhang
                       else ""))
            self.buckets = buckets
            self.max_len = int(cache_max_len) if cache_max_len else \
                _round_up(buckets[-1] + self.max_new_tokens + overhang)
            if self.max_len < buckets[-1] + self.max_new_tokens + overhang:
                raise ValueError(
                    f"cache_max_len {self.max_len} < largest bucket "
                    f"{buckets[-1]} + max_new_tokens {self.max_new_tokens}"
                    + (f" + speculative verify-window overhang {overhang} "
                       "(the last window's unaccepted draft tokens still "
                       "write their KV before rollback)" if overhang
                       else "")
                    + "; the shared ring cache would wrap under a "
                    "full-length request")

            # ------------------------------------------------- paged KV cache
            # block-table paged cache + shared-prefix reuse (ROADMAP item 3):
            # K/V live in a pool of fixed-size pages, each slot holds an
            # int32 page table, admission is gated on FREE PAGES (memory)
            # as well as free slots (batch lanes), and identical prompt
            # prefixes reference the same pages copy-on-write.
            self._alloc = None
            self._overhang = overhang
            if bool(_opt(paged, "paged", False)):  # lint: host-sync-ok (config coercion)
                from ..generation.paged_cache import PageAllocator
                env_ps = _env_int("PADDLE_KV_PAGE_SIZE",
                                  "serving.kv_page_size")
                ps = int(_opt(kv_page_size, "kv_page_size",
                              128 if env_ps is None else env_ps))
                if ps < 1 or self.max_len % ps:
                    raise ValueError(
                        f"kv_page_size {ps} must divide the cache length "
                        f"{self.max_len} (PADDLE_KV_PAGE_SIZE / "
                        "enable_serving(kv_page_size=...))")
                self.page_size = ps
                self.pages_per_row = self.max_len // ps
                # default pool: the dense cache's exact HBM footprint
                # (max_batch rows of max_len) plus the reserved null page —
                # the capacity win comes from requests that don't USE
                # max_len and from shared prefixes, not from a bigger pool
                n_pages = int(_opt(kv_pages, "kv_pages",
                                   self.max_batch * self.pages_per_row + 1))
                # a pool that cannot cover ONE max-size request would stall
                # the queue head forever with no error — same fail-fast
                # contract as the dense "ring would wrap" check above
                worst = -(-(buckets[-1] + self.max_new_tokens + overhang)
                          // ps)
                if n_pages - 1 < worst:
                    raise ValueError(
                        f"kv_pages {n_pages} (1 reserved) cannot hold one "
                        f"full-size request: bucket {buckets[-1]} + "
                        f"max_new_tokens {self.max_new_tokens}"
                        + (f" + speculative overhang {overhang}" if overhang
                           else "")
                        + f" needs {worst} pages of {ps}; raise kv_pages "
                        "or kv_page_size")
                self._alloc = PageAllocator(n_pages, ps)
                self._page_seen: Dict[str, int] = {}
                self._pending_pages: Dict[int, tuple] = {}
                self._row_pages: List[Optional[list]] = [None] * self.max_batch
                self._page_blocked = False
                # (req.id, allocator version) of the last head whose plan
                # failed to commit: while nothing changed in the pool, the
                # pump loop skips re-hashing the prompt and re-walking the
                # registry on every iteration
                self._blocked_key = None

            # ---------------------------------------------- chunked prefill
            # head-of-line fix (ROADMAP item 2a): prompts longer than
            # prefill_chunk_tokens are admitted C tokens at a time, ONE
            # chunk per scheduler iteration, interleaved with the decode
            # dispatch — in-flight streams keep producing tokens while the
            # long prompt fills a persistent batch-1 SIDE cache that the
            # ordinary admit program installs at the final chunk. Opt-in
            # (kwarg > enable_serving > PADDLE_PREFILL_CHUNK_TOKENS); paged
            # engines require page alignment so every completed chunk ends
            # on a page boundary the span-install can commit.
            ct = _opt(prefill_chunk_tokens, "prefill_chunk_tokens",
                      _env_int("PADDLE_PREFILL_CHUNK_TOKENS",
                               "serving.prefill_chunk_tokens"))
            self.prefill_chunk_tokens = None
            if ct is not None and self._bd is not None:
                raise ValueError(
                    "chunked prefill attends causally inside a chunk; "
                    "block_diffusion prefills block-causally, inline")
            if ct is not None:
                ct = int(ct)
                if ct < 1:
                    raise ValueError(
                        f"prefill_chunk_tokens {ct} must be >= 1 "
                        "(PADDLE_PREFILL_CHUNK_TOKENS / "
                        "enable_serving(prefill_chunk_tokens=...))")
                if self._alloc is not None and ct % self.page_size:
                    raise ValueError(
                        f"prefill_chunk_tokens {ct} must be a multiple of "
                        f"kv_page_size {self.page_size}: every completed "
                        "chunk must end on a page boundary so its span "
                        "installs into whole committed pages")
                # the final chunk pads to the chunk width, so the side
                # cache writes up to ceil(bucket/C)*C positions — past
                # max_len the ring modulo would WRAP the write onto the
                # prompt's own prefix (silent corruption, not an error)
                padded_top = -(-buckets[-1] // ct) * ct
                if ct < buckets[-1] and padded_top > self.max_len:
                    raise ValueError(
                        f"prefill_chunk_tokens {ct}: the largest bucket "
                        f"{buckets[-1]} pads to {padded_top} chunked "
                        f"tokens, past the cache length {self.max_len} — "
                        "the final padded chunk would wrap the ring onto "
                        "the prompt prefix; raise prefill_chunk_tokens or "
                        "cache_max_len")
                self.prefill_chunk_tokens = ct
            # chunking can only ever trigger for prompts LONGER than one
            # chunk; with every bucket at or under C the programs would be
            # dead weight in warmup
            self._chunk_enabled = (self.prefill_chunk_tokens is not None
                                   and self.prefill_chunk_tokens
                                   < buckets[-1])
            self._chunking = None   # the (single) in-flight chunked
            #                         admission's scheduler state

            # the step mode (serving/programs.py) is chosen here, once:
            # the scheduler below calls it and never asks which it is
            if self._bd is not None:
                self._mode = programs.BlockDiffusion(self._bd)
            elif self._spec is not None:
                self._mode = programs.Speculative(self._cfg, self._spec,
                                                  self.max_len)
            else:
                self._mode = programs.Decode(self._cfg)
            net = programs.Network(layer, self._sp, cache_kw,
                                   self._mode.forward_kw)
            # executable persistence: every program warmup() compiles goes
            # through jit.compile_cache (this store, or the process default
            # when None) so a relaunched engine loads instead of recompiling
            self._exe_store = executable_store

            # ------------------------------------------------------- state
            self._state = tuple(self._sp.vals)
            if seed is not None:
                self._key = jax.random.PRNGKey(int(seed))
            elif self._cfg.do_sample:
                from ..core import random as _random
                self._key = _random.next_key()
            else:
                self._key = jax.random.PRNGKey(0)  # greedy: never consumed

            B, cap = self.max_batch, self.max_new_tokens
            sds = jax.ShapeDtypeStruct
            cache_aval = jax.eval_shape(
                lambda s, i, p, k: programs.prefill_fn(
                    net, s, i, p, k, self._cfg, self.max_len),
                self._state, sds((B, buckets[0]), jnp.int32),
                sds((B,), jnp.int32), self._key)[1]
            # a model whose cache holds per-lane state beside its KV
            # (generation/hybrid_cache.py): what cannot carry a state yet
            # is refused here, with the reason, not served wrong
            if getattr(cache_aval, "state", None) is not None:
                refusal = (
                    (self._spec is not None,
                     "speculative decoding rolls a lane's cache back to "
                     "the accepted length after every verify window"),
                    (self._chunk_enabled,
                     "chunked prefill hands a side cache from chunk to "
                     "chunk and installs it in spans"))
                for on, why in refusal:
                    if on:
                        raise ValueError(
                            f"{why}; the model's cache holds per-lane "
                            "state of a fixed width beside its KV "
                            f"({cache_aval!r}), which can only move "
                            "forward: serve it by plain decode with "
                            "inline prefill")
            with flight_recorder.span("setup.cache_alloc") as alloc_sp:
                quant = getattr(cache_aval, "k_scale", None) is not None
                if self._alloc is not None:
                    # paged pool: the cache kind the model's prefill
                    # returned states its own paged form (layers / heads /
                    # head_dim / dtype kept, rows replaced by the page pool
                    # + tables; whatever else it holds a lane kept as is)
                    cache_aval = cache_aval.paged(
                        self._alloc.n_pages, self.page_size,
                        self.pages_per_row)
                self._cache = _host_zeros(cache_aval)
                # the low-bit accounting satellites: the kv_dtype info gauge
                # (what this engine serves — the router reads it beside the
                # capacity numbers) and, when quantized, the HBM bytes the int8
                # storage saved vs the wide dtype (host arithmetic over shapes)
                self._clips_seen = 0
                if quant:
                    # the wide dtype the cache WOULD have carried: the serving
                    # compute dtype when a precision mode set one, else the
                    # model's own float param dtype (a model.bfloat16() under
                    # default precision serves a bf16 cache — name check
                    # because np.issubdtype(bfloat16, floating) is False)
                    wide_dt = self._sp.compute_dtype
                    if wide_dt is None:
                        wide_dt = next(
                            (v.dtype for v in self._sp.vals
                             if np.issubdtype(np.dtype(v.dtype), np.floating)
                             or np.dtype(v.dtype).name == "bfloat16"),
                            np.float32)
                    wide_dt = np.dtype(wide_dt)
                    self._kv_dtype_label = "int8"
                    saved = 2 * int(np.prod(self._cache.k.shape)) \
                        * (wide_dt.itemsize - 1) \
                        - 2 * int(np.prod(self._cache.k_scale.shape)) * 2
                    monitor.record_kv_quant(bytes_saved=max(0, saved))
                else:
                    # the dtype the cache ACTUALLY carries, from its own aval
                    self._kv_dtype_label = np.dtype(cache_aval.k.dtype).name
                monitor.record_kv_dtype(self._kv_dtype_label)
                self._lanes = jax.device_put(self._mode.lanes(B, cap))
                # bytes handed to device_put; the transfer is not awaited
                # here (the first program that reads them waits for it)
                def nbytes(tree):
                    return sum(int(a.nbytes)
                               for a in jax.tree_util.tree_leaves(tree))
                state_bytes = getattr(self._cache, "state_bytes", 0)
                # what one admission installs of it: the slot's row
                self._state_row_bytes = state_bytes // B
                alloc_sp.set(
                    bytes=nbytes((self._cache, self._lanes)),
                    kv_bytes=nbytes(self._cache) - state_bytes,
                    state_bytes=state_bytes)

            self._programs = programs.program_table(
                net, self._mode, self._cfg, state=self._state,
                cache=self._cache, lanes=self._lanes, key=self._key,
                buckets=buckets, max_len=self.max_len,
                chunk=(self.prefill_chunk_tokens if self._chunk_enabled
                       else None),
                pages_per_row=(None if self._alloc is None
                               else self.pages_per_row))

            # chunked prefill's persistent batch-1 SIDE cache: the same
            # dense row cache a bucket prefill would produce (max_len long,
            # quant sidecars included), host-built zeros like the lanes
            # above. Rebuilt from host zeros after every chunked admission
            # or abort — the admit program DONATES it, so the
            # buffer is gone either way, and the rebuild is also what
            # resets kv_len to 0 and zeroes the quant clip counter between
            # requests.
            self._row_cache = None
            self._row_cache_aval = None
            if self._chunk_enabled:
                self._row_cache_aval = self._programs[
                    ("chunk", self.prefill_chunk_tokens)].args["row_cache"]
                self._row_cache = _host_zeros(self._row_cache_aval)

            self._slots: List[Optional[Request]] = [None] * B
            self._slot_used = [False] * B          # reuse detection
            self._queue = collections.deque()
            self._qlock = threading.Lock()
            self._pump_lock = threading.RLock()
            self._thread: Optional[threading.Thread] = None
            self._exes: Dict = {}
            self._warm = False
            self._shutdown = False
            self._steps_since_poll = 0
            # device programs dispatched so far, and the decode steps a
            # blocking read has seen land: a read is told which program
            # it waits for (``_mark``), and its serve.sync says how many
            # steps lay before that one (steps_queued) and how many
            # programs behind it (ahead)
            self._seq = 0
            self._steps_landed = 0
            self._window_t0_ns: Optional[int] = None
            self._window_steps = 0
            # emitted_tokens / polls: every lane's progress as the polls saw
            # it (Request.n_emitted is brought up to date at the same poll)
            self.stats = dict(submitted=0, admitted=0, completed=0,
                              cancelled=0, rejected=0, slots_reused=0,
                              decode_steps=0, prefills=0, prefill_chunks=0,
                              spec_proposed=0, spec_accepted=0,
                              emitted_tokens=0, polls=0,
                              diffusion_forwards=0, diffusion_commits=0)
            # top-K most expensive terminal requests (heap of
            # (total_s, req id, cost dict)) — the /slo cost table
            self._cost_top: List[tuple] = []
            self._cost_topk = 10
            # goodput ledger (serve.goodput.* family): dispatch windows and
            # admissions charge compute (or compile when a retrace happened
            # inside the window), serve_forever's empty-queue sleeps charge
            # idle, preemption drains charge preemption_recovery; the
            # unattributed residual folds into idle — an un-pumped engine
            # is waiting, not computing. Started after warmup so the
            # one-time compile storm doesn't poison steady-state goodput.
            from ..core import goodput as goodput_mod
            self._goodput = goodput_mod.GoodputLedger(
                "serve", default_bucket="idle")
            # ------------------------------------------------ HBM planning
            # admission control for MEMORY, before a single buffer compiles:
            # with a budget declared (kwarg > enable_serving > env), the
            # static planner (analysis.memory) predicts the engine's peak —
            # weights + kv pool + lanes resident, plus the decode/admission
            # transients — and a config that cannot fit fails HERE, not as
            # an on-device OOM under traffic (the kv_pages-too-small
            # fail-fast contract). health() reports the headroom.
            self._mem_summary = None
            self.hbm_budget = None
            from ..analysis.memory import resolve_hbm_budget
            explicit_budget = _opt(hbm_budget, "hbm_budget", None)
            if explicit_budget is not None:
                # an explicit (kwarg / enable_serving) garbage budget
                # RAISES: the operator asked for a gate and must get one
                self.hbm_budget = resolve_hbm_budget(explicit_budget)
            else:
                try:
                    self.hbm_budget = resolve_hbm_budget()
                except ValueError as e:
                    # a garbage ENV budget must not crash (or silently
                    # gate) the engine: swallow observably, serve ungated
                    monitor.record_swallowed("serving.hbm_budget", e)
            if self.hbm_budget is not None:
                mp = self.memory_plan()
                if mp["predicted_peak_bytes"] > self.hbm_budget:
                    raise ValueError(
                        f"predicted peak HBM {mp['predicted_peak_bytes']} "
                        f"bytes exceeds hbm_budget {self.hbm_budget} "
                        f"(weights {mp['weights_bytes']}, kv cache "
                        f"{mp['kv_cache_bytes']}, lanes "
                        f"{mp['lanes_bytes']}, decode peak "
                        f"{mp['decode_peak_bytes']}, admission prefill "
                        f"peak {mp['prefill_peak_bytes']}); shrink "
                        "max_batch/cache_max_len/kv_pages or quantize the "
                        "cache (kv_cache_dtype='int8'), or raise the "
                        "budget (PADDLE_HBM_BUDGET / "
                        "enable_serving(hbm_budget=...))")
            # live export surface: opt-in via telemetry_port= (here or in
            # Config.enable_serving) or PADDLE_TELEMETRY_PORT. Started
            # BEFORE warmup so /healthz answers while the replica warms
            # (/readyz stays 503 until warm — a router must not route yet).
            # A bind failure (port still held by a drained-but-not-stopped
            # predecessor) must never crash the engine it would measure:
            # the engine serves un-scraped, the swallow is logged.
            self.telemetry = None
            tp = _opt(telemetry_port, "telemetry_port", None)
            from ..core import telemetry_server
            try:
                if tp is not None:
                    self.telemetry = telemetry_server.TelemetryServer(
                        port=int(tp)).start().attach_engine(self)
                else:
                    self.telemetry = telemetry_server.start_from_env(self)
            except OSError as e:
                monitor.record_swallowed("serving.telemetry_bind", e)
            # fleet plane opt-in (PADDLE_FLEET_STORE=host:port, exported by
            # the launcher's --fleet_store): publish this replica's metrics
            # + health to the shared TCPStore; on the elected rank the
            # member also aggregates, and the aggregator rides this
            # process's telemetry server at /fleet/*. A bad address or an
            # unreachable store must never take the replica down.
            self.fleet = None
            try:
                from ..distributed import fleet_telemetry
                self.fleet = fleet_telemetry.start_from_env(
                    health_fn=self.health)
                if self.fleet is not None and \
                        self.fleet.aggregator is not None and \
                        self.telemetry is not None:
                    self.telemetry.attach_aggregator(self.fleet.aggregator)
            except Exception as e:
                monitor.record_swallowed("serving.fleet_start", e)
            if warmup:
                try:
                    self.warmup()
                except BaseException:
                    # constructor abort: the caller never gets a handle, so
                    # shutdown() can never release the port — stop the
                    # server here or it leaks (bound, answering "engine
                    # gone" forever, blocking the retried engine's bind)
                    if self.telemetry is not None:
                        self.telemetry.stop()
                        self.telemetry = None
                    if self.fleet is not None:
                        self.fleet.stop()
                        self.fleet = None
                    raise
            self._goodput.start()

    # ------------------------------------------------------ compilation
    def _ensure_eval(self):
        # a fit() loop sharing this layer flips it back to train mode
        # every batch; tracing then would bake active dropout into the
        # served program — or close over extra RNG inputs and break the
        # compiled call signature. Same contract as
        # GenerationSession._ensure_eval: force eval at every trace
        # point (executable dispatches are mode-independent).
        if self.network.training:
            self.network.eval()

    def _program_signature(self, cache_key):
        """Structural identity of one scheduler program WITHOUT tracing
        it (the store's traceless manifest key): network code + weights
        structure, the full bucket/shape/sampling/precision config, and
        the engine's own lane avals. None (→ traced path) when the
        network has no deterministic description."""
        from ..jit import compile_cache
        sig = compile_cache.network_signature(self.network)
        if sig is None:
            return None
        sig.update(
            program=("serving",) + tuple(cache_key),
            generation=repr(self._cfg),
            speculative=repr(self._spec),
            block_diffusion=repr(self._bd),
            buckets=tuple(self.buckets),
            shape=(self.max_batch, self.max_len, self.max_new_tokens),
            paged=(None if self._alloc is None else
                   (self.page_size, self.pages_per_row,
                    self._alloc.n_pages)),
            # the quant geometry: cache dtype + weight packing change
            # every program's operand layout, so they key the manifest
            kv_cache=self.cache_dtype,
            weight_bits=sorted(self._sp.int4) if self._sp.int4 else None,
            precision=(self.config.precision,
                       getattr(self.config, "_int8_compute", False)),
            operands=compile_cache.aval_signature(self._state))
        return sig

    def _compiled(self, cache_key):
        """One warm program of the table (``serving/programs.py``): the
        executable comes from the store on a warm relaunch (manifest
        hit: zero traces, zero XLA compiles) or from the record's
        lowering and a fresh ``compile()`` that is then persisted."""
        exe = self._exes.get(cache_key)
        if exe is None:
            from ..jit import compile_cache
            prog = self._programs[cache_key]
            self._ensure_eval()
            # a compile after warmup means live traffic hit a shape no
            # executable was built for — exactly what the steady-state
            # no-retrace gate (jit.compile{cause=new_shape} == 0) guards
            monitor.record_retrace(
                "first" if not self._warm else "new_shape")
            label = "serving." + ".".join(str(p) for p in cache_key)
            exe = compile_cache.build_or_load(
                self._program_signature(cache_key), prog.lower,
                store=self._exe_store,
                extra=dict(kind=label, donation=prog.donation),
                label=label)
            self._exes[cache_key] = exe
        return exe

    def _exe_prefill(self, bucket: int):
        return self._compiled(("prefill", bucket))

    @property
    def _finished(self):
        return self._lanes.finished

    @property
    def _steps(self):
        """Tokens each lane has made so far ([batch] int32, on the
        device): the lanes' own, under the name readers know."""
        return self._lanes.steps

    def warmup(self):
        """Compile every program the scheduler can dispatch (one
        prefill per bucket + the decode/admit/free trio, plus the
        chunk-prefill pair — and the paged span install — when chunked
        prefill is enabled). After this, live traffic only ever hits
        warm executables; any later compile is recorded as
        ``jit.compile{cause=new_shape}``."""
        with flight_recorder.span("setup.warmup"):
            for key in self._programs:
                self._compiled(key)
        self._warm = True
        return self

    # -------------------------------------------------------- admission
    def submit(self, prompt, params: Optional[RequestParams] = None) \
            -> Request:
        """Enqueue one prompt; returns the Future-style handle
        immediately. Raises :class:`QueueFull` at the queue-depth bound
        and ``ValueError`` for prompts no compiled bucket can hold —
        admission control happens here, not deep in the scheduler."""
        if isinstance(prompt, Tensor):
            prompt = prompt._data
        ids = np.asarray(prompt).reshape(-1).astype(np.int32)  # lint: host-sync-ok (pre-dispatch input prep)
        if ids.size < 1:
            raise ValueError("empty prompt")
        if ids.size > self.buckets[-1]:
            raise ValueError(
                f"prompt of {ids.size} tokens exceeds the largest "
                f"compiled prefill bucket {self.buckets[-1]}")
        self._mode.check_prompt(ids)
        params = params if params is not None else RequestParams()
        budget = self.max_new_tokens if params.max_new_tokens is None \
            else int(params.max_new_tokens)
        if not 1 <= budget <= self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {budget} outside [1, "
                f"{self.max_new_tokens}] (the compiled budget; raise it "
                "in enable_generation())")
        dl = params.deadline_s if params.deadline_s is not None \
            else self.default_deadline_s
        deadline = None if dl is None \
            else time.monotonic() + float(dl)  # lint: host-sync-ok (config coercion)
        req = Request(ids, params, budget, deadline, engine=self)
        with self._qlock:
            if self._shutdown:
                req._finish(RequestStatus.REJECTED, "shutdown")
                self.stats["rejected"] += 1
                monitor.record_serve_request("rejected")
                raise RuntimeError(
                    "serving engine is shut down; no new requests")
            if len(self._queue) >= self.max_queue:
                reason = self._rejection_reason()
                req._finish(RequestStatus.REJECTED, reason)
                self.stats["rejected"] += 1
                monitor.record_serve_request("rejected")
                raise QueueFull(
                    f"request queue at bound ({self.max_queue}): "
                    f"{reason}", reason=reason, request=req)
            if self.trace_sample and req.id % self.trace_sample == 0:
                req.traced = True
            self._queue.append(req)
            self.stats["submitted"] += 1
            qdepth = len(self._queue)
            monitor.record_serve_queue_depth(qdepth)
        if flight_recorder.enabled:
            flight_recorder.record("serve.submit", req=req.id,
                                   prompt_len=int(ids.size),
                                   budget=budget, queue_depth=qdepth)
        return req

    def _rejection_reason(self) -> str:
        """The structured health reason a queue-bound rejection carries
        on BOTH the handle and the QueueFull (callers hold ``_qlock``):
        the same no_free_pages/no_free_slots distinction ``health()``
        suffixes onto its 503 reason, observable per-request — a
        router re-routes memory pressure and slot pressure to a
        different survivor set. Bare ``queue_full`` means the blocker
        is not yet known (a submit burst filled the queue between
        scheduler steps while slots were still free)."""
        if self._alloc is not None and self._page_blocked:
            return "queue_full:no_free_pages"
        if sum(s is not None for s in self._slots) >= self.max_batch:
            return "queue_full:no_free_slots"
        return "queue_full"

    def _queue_room(self) -> bool:
        with self._qlock:
            return len(self._queue) < self.max_queue

    @property
    def busy(self) -> bool:
        """True while anything is queued or occupies a slot."""
        with self._qlock:
            if self._queue:
                return True
        return any(s is not None for s in self._slots)

    # -------------------------------------------------------- scheduler
    def step(self):
        """One scheduler iteration: dispatch the admissions of queued
        requests into free slots (short prompts inline, long ones one
        CHUNK per iteration when chunked prefill is on), dispatch one
        fixed-batch decode step for the running slots, and only then
        wait for the last admission's first token; advance the in-flight
        chunked prefill, poll completions every ``poll_every`` steps.
        Every blocking read comes after the dispatch of what follows the
        value it reads: the decode step is queued behind the prefills
        before their tokens are waited for, and before the chunk's sync
        (in-flight streams overlap the chunk's device time instead of
        stalling behind a whole long prefill — the head-of-line fix); a
        poll of a full engine dispatches one more step before it reads
        (``_poll_lanes``)."""
        with self._pump_lock, flight_recorder.span("serve.step") as sp:
            steps0, gc0 = self.stats["decode_steps"], flight_recorder.gc_ns()
            admitted = self._admit_ready()
            live = sum(s is not None
                       and s.status is RequestStatus.RUNNING
                       for s in self._slots)
            if live:
                self._dispatch_decode()
            self._land(admitted)
            self._advance_chunked()
            if self._steps_since_poll >= self.poll_every:
                self._poll()
            sp.set(decode=self.stats["decode_steps"] - steps0, live=live,
                   queued=len(self._queue),
                   gc_ms=(flight_recorder.gc_ns() - gc0) / 1e6)

    def _unblock_if(self, req: Request):
        """Clear the page-pressure flag when the request it was
        computed FOR leaves the queue (deadline sweep, drain): a stale
        flag would steer the router's no_free_pages/no_free_slots
        signal at the next health() until a slot freed."""
        if self._alloc is not None and self._blocked_key is not None \
                and self._blocked_key[0] == req.id:
            self._blocked_key = None
            self._page_blocked = False

    def _pop_queue(self) -> Optional[Request]:
        with self._qlock:
            while self._queue:
                req = self._queue[0]
                if req.deadline is not None and \
                        time.monotonic() > req.deadline:
                    self._queue.popleft()
                    monitor.record_serve_queue_depth(len(self._queue))
                    self._unblock_if(req)
                    self._cancel(req, "deadline")
                    continue
                if self._needs_chunk(req) and self._chunking is not None:
                    # ONE chunked prefill at a time, strict FIFO: the
                    # long head waits (un-popped, pages uncommitted)
                    # until the active chunked admission finishes —
                    # admitting a later request past it would starve it
                    return None
                if self._alloc is not None:
                    # admission counts FREE PAGES, not just free slots:
                    # the head request's page plan (its prompt prefix
                    # hashed against the registry, its own budget +
                    # speculative overhang) must commit before the slot
                    # is spent. A pool too full leaves the head QUEUED —
                    # memory pressure, which health() reports as
                    # no_free_pages so a router can tell it from
                    # slot/admission pressure. While the pool state is
                    # UNCHANGED since the head last failed to commit,
                    # the pump loop skips the (identical) replan
                    # entirely instead of burning hash+registry walks
                    # every sub-millisecond iteration.
                    ver = self._alloc.version
                    if self._blocked_key == (req.id, ver):
                        self._page_blocked = True
                        return None
                    with flight_recorder.span("serve.plan",
                                              req=req.id) as sp:
                        plan = self._alloc.plan(
                            req.prompt, req.budget + self._overhang)
                        pages = self._alloc.commit(plan)
                        if pages is None:
                            sp.set(blocked=1)
                        else:
                            sp.set(pages=len(pages),
                                   shared=int(plan.shared_len))
                    if pages is None:
                        # a failed commit may still have reclaimed
                        # cached pages — key on the post-attempt version
                        self._blocked_key = (req.id, self._alloc.version)
                        self._page_blocked = True
                        return None
                    self._blocked_key = None
                    self._page_blocked = False
                    self._pending_pages[req.id] = (pages, plan)
                self._queue.popleft()
                monitor.record_serve_queue_depth(len(self._queue))
                return req
        return None

    def _needs_chunk(self, req: Request) -> bool:
        """Chunked admission applies to prompts LONGER than one chunk
        (shorter ones inline-prefill in a single dispatch, as before)."""
        return self._chunk_enabled and \
            req.prompt.size > self.prefill_chunk_tokens

    def _admit_ready(self) -> Optional[tuple]:
        """Fill the free slots from the queue. An inline admission's
        prefill and admit program are dispatched and not waited for;
        the one before it is landed first (``_land``: its own admit
        program is queued behind that wait, and runs while the host
        prepares this one). So a prefill's row — a whole batch-1 cache,
        256 MiB at 6.7B widths, alive until its admit program has run —
        never has more than one other beside it, however many slots are
        free. The last admission comes back unlanded: ``step`` queues
        the decode step behind it before it waits."""
        pending = None
        for slot, occupant in enumerate(self._slots):
            if occupant is not None:
                continue
            req = self._pop_queue()
            if req is None:
                break
            self._land(pending)
            pending = None
            try:
                if self._needs_chunk(req):
                    self._begin_chunked(req, slot)
                else:
                    pending = self._admit(req, slot)
            except Exception as e:
                # the request left the queue but reached no slot: it
                # MUST still go terminal or its Future would hang
                # forever (and its committed pages must return to the
                # free list); the engine keeps serving the others
                if self._chunking is not None and \
                        self._chunking["req"] is req:
                    self._chunking = None
                    self._slots[slot] = None  # lint: lock-discipline-ok (admission runs under the caller's pump lock)
                self._release_pending(req)
                self._cancel(req, f"admission error: "
                                  f"{type(e).__name__}: {e}",
                             label="error")
                monitor.record_swallowed("serving.admit", e)
        return pending

    def _land(self, admitted: Optional[tuple]) -> None:
        """Wait for the first token of an admission ``_admit`` dispatched
        (None: nothing to wait for): a wait on a prefill's own token
        returns when that prefill lands, whatever is queued behind it,
        so the TTFT stamp is where it was. A prefill that failed on the
        device surfaces HERE, its request already in its slot: the slot
        is evicted (lane masked, row reset, pages back on the free list)
        and the request goes terminal; the others are served."""
        if admitted is None:
            return
        req, slot, bucket, t_admit_ns, tok, after = admitted
        t0 = flight_recorder.now_ns()
        try:
            _, t1 = self._sync("prefill", tok.block_until_ready, after)
        except Exception as e:
            t1 = flight_recorder.now_ns()
            self._evict(slot, req,
                        f"admission error: {type(e).__name__}: {e}",
                        label="error")
            monitor.record_swallowed("serving.admit", e)
        else:
            self._first_token(req, t_admit_ns, t1, bucket)
            # the wait is admission wall too (the dispatch was charged
            # when its serve.admit closed)
            self._charge_admission(req, (t1 - t0) * 1e-9, False)
        # the wait must not be attributed to per-token decode latency:
        # the window restarts where it returned, holding the steps
        # dispatched behind the admission
        self._window_steps = self.stats["decode_steps"] - after[1]
        self._window_t0_ns = t1

    def _run(self, key, *operands):
        """Dispatch one warm program of the table under its
        ``serve.dispatch`` (``program``: the key's name). Nothing
        waits."""
        exe = self._compiled(key)
        with flight_recorder.span("serve.dispatch", program=key[0]):
            out = exe(*operands)
        self._seq += 1
        return out

    def _mark(self) -> tuple:
        """The last program dispatched, for the read that will wait for
        it: (programs dispatched, decode steps among them)."""
        return self._seq, self.stats["decode_steps"]

    def _sync(self, site: str, read, after: Optional[tuple] = None):
        """One blocking device read under a ``serve.sync`` span:
        ``(what read() returned, the stamp at which it returned)``.
        ``after`` is the ``_mark`` of the program whose output ``read``
        waits for (the last one dispatched when not given)."""
        seq, steps = after or self._mark()
        with flight_recorder.span(
                "serve.sync", site=site,
                steps_queued=max(steps - self._steps_landed, 0),
                ahead=self._seq - seq) as sp:
            out = read()
        self._steps_landed = max(self._steps_landed, steps)
        return out, sp.end_ns or flight_recorder.now_ns()

    def _dequeued(self, req: Request, sp, bucket: int) -> int:
        """``req`` has left the queue and ``sp`` (its ``serve.admit``)
        is open: the one stamp that ends its queue wait, starts its
        prefill and is its ``admitted_at``."""
        t = sp.start_ns or flight_recorder.now_ns()
        req.admitted_at = t * 1e-9
        if flight_recorder.enabled:
            req.stage_span("serve.queue_wait",
                           int(req.submitted_at * 1e9), t, bucket=bucket)
        return t

    def _first_token(self, req: Request, t_admit_ns: int, t_ns: int,
                     bucket: int):
        """The prefill's sync returned at ``t_ns``: the TTFT
        measurement point."""
        req.first_token_at = t_ns * 1e-9
        monitor.record_serve_ttft(req.first_token_at - req.submitted_at)
        monitor.record_generation(prefill_steps=1)
        self.stats["prefills"] += 1
        if flight_recorder.enabled:
            req.stage_span("serve.prefill", t_admit_ns, t_ns,
                           bucket=bucket)
        if req.traced:
            req._t_seg_ns = t_ns

    def _charge_admission(self, req: Request, dt: float, retraced: bool):
        """Admission wall time is compute in the goodput ledger — or
        compile, when the dispatch retraced (a cold bucket slipping past
        warmup spends the window tracing, not prefilling). Cost
        attribution mirrors the ledger charge: the request owns exactly
        the admission wall the ledger books, so per-request costs
        reconcile against the compute bucket."""
        req._cost_prefill_s += dt
        self._goodput.charge("compile" if retraced else "compute", dt)

    def _admit(self, req: Request, slot: int) -> tuple:
        """Dispatch one inline admission (``_admit_inner``) under its
        ``serve.admit``; returns what ``_land`` waits for it with."""
        retraces0 = monitor.retrace_count()
        bucket = next(b for b in self.buckets if b >= req.prompt.size)
        sp = flight_recorder.span("serve.admit", req=req.id, slot=slot,
                                  bucket=bucket,
                                  prompt=int(req.prompt.size),
                                  state_bytes=self._state_row_bytes)
        t0 = 0
        try:
            with sp:
                t0 = self._dequeued(req, sp, bucket)
                tok, after = self._admit_inner(req, slot, bucket)
        finally:
            t1 = sp.end_ns or flight_recorder.now_ns()
            self._charge_admission(
                req, (t1 - (t0 or t1)) * 1e-9,
                monitor.retrace_count() > retraces0)
        return req, slot, bucket, t0, tok, after

    def _admit_inner(self, req: Request, slot: int, bucket: int) -> tuple:
        """The prefill, then the admit program on its outputs: both
        dispatched, neither waited for (the admit and the mode's
        ``first`` take ``tok`` and ``fin`` as the device arrays they
        are). Returns the token to wait on and the prefill's mark: the
        TTFT measurement point is that wait's return — one small sync
        per ADMISSION (not per decode step), made by ``_land`` once the
        iteration's decode step is queued behind."""
        ids = np.full((1, bucket), self._cfg.pad_value, np.int32)
        ids[0, :req.prompt.size] = req.prompt
        plen = np.array([self._mode.prefill_len(req.prompt)], np.int32)
        exe = self._exe_prefill(bucket)
        with flight_recorder.span("serve.dispatch", program="prefill"):
            tok, row_cache, self._key, fin = exe(
                self._state, jnp.asarray(ids), jnp.asarray(plen),
                self._key)
        self._seq += 1
        after = self._mark()
        self._install(req, slot, row_cache, tok, fin)
        return tok, after

    def _table_row(self, pages) -> np.ndarray:
        """A row's page table: its pages in position order; unused
        table slots stay 0 (the null page)."""
        table_row = np.zeros((self.pages_per_row,), np.int32)
        table_row[:len(pages)] = pages
        return table_row

    def _install(self, req: Request, slot: int, row_cache, tok, fin,
                 installed: int = 0):
        """The admit program, for an inline and a chunked admission
        alike: the prefill's row into ``slot``'s cache row, the mode's
        first values into its lanes; then the request is RUNNING.
        ``installed``: positions the chunk spans have already put in
        their pages."""
        where, pages, plan = (), None, None
        if self._alloc is not None:
            # shared prefix pages first, then the freshly allocated
            # private ones. start marks the first position the install
            # actually writes — everything below it is referenced shared
            # content or an installed span. The pending entry is popped
            # only AFTER the install lands: an admit failure must leave
            # it for _release_pending to roll back.
            pages, plan = self._pending_pages[req.id]
            where = (self._table_row(pages),
                     np.int32(max(int(plan.shared_len), installed)))
        self._cache, self._lanes = self._run(
            ("admit",), self._cache, self._lanes, np.int32(slot), row_cache,
            self._mode.first(req.prompt, req.budget, tok, fin), *where)
        if self._alloc is not None:
            # the row now references its pages; register the prompt's
            # full pages so later identical prefixes hit them
            self._pending_pages.pop(req.id)
            self._alloc.register(plan, pages)
            self._row_pages[slot] = pages
        if self._slot_used[slot]:
            self.stats["slots_reused"] += 1
        self._slot_used[slot] = True  # lint: lock-discipline-ok (admission runs under the caller's pump lock)
        self._slots[slot] = req  # lint: lock-discipline-ok (admission runs under the caller's pump lock)
        req.status = RequestStatus.RUNNING
        self.stats["admitted"] += 1
        monitor.record_serve_slot_occupancy(
            sum(s is not None for s in self._slots) / self.max_batch)

    # ------------------------------------------------- chunked prefill
    def _begin_chunked(self, req: Request, slot: int):
        """Reserve ``slot`` for a long prompt and park it in
        PENDING_PREFILL: the device lane stays masked (finished True,
        kv_len 0, null page table) while ``_advance_chunked`` feeds the
        prompt into the side cache one chunk per scheduler iteration.
        Host bookkeeping only — no dispatch happens here."""
        C = self.prefill_chunk_tokens
        plen = int(req.prompt.size)
        n = -(-plen // C)
        with flight_recorder.span("serve.admit", req=req.id, slot=slot,
                                  bucket=n * C, prompt=plen,
                                  chunks=n) as sp:
            t_ns = self._dequeued(req, sp, n * C)
            ids = np.full((1, n * C), self._cfg.pad_value, np.int32)
            ids[0, :plen] = req.prompt
            shared = 0
            if self._alloc is not None:
                shared = int(self._pending_pages[req.id][1].shared_len)
            self._chunking = dict(req=req, slot=slot, plen=plen, n=n,
                                  next=0, ids=ids, shared=shared,
                                  decode_steps=0, t_ns=t_ns)
            self._slots[slot] = req  # lint: lock-discipline-ok (admission runs under the caller's pump lock)
            req.status = RequestStatus.PENDING_PREFILL
            monitor.record_serve_slot_occupancy(
                sum(s is not None for s in self._slots) / self.max_batch)

    def _advance_chunked(self):
        """Run AT MOST ONE chunk of the in-flight chunked prefill: the
        chunk program over the side cache (plus the paged span install),
        one blocking sync, then hand the device back to decode. The
        final chunk samples the first token and runs the ordinary admit
        program — TTFT lands there. Deadline/abort semantics live here
        because ``_poll`` skips PENDING_PREFILL slots entirely."""
        st = self._chunking
        if st is None:
            return
        req = st["req"]
        if req.deadline is not None and \
                time.monotonic() > req.deadline:
            self._abort_chunked("deadline")
            return
        # same goodput/cost contract as _admit: each chunk's dispatch
        # wall is compute (or compile, when it retraced), charged to
        # the request's prefill cost — chunked admissions sum their
        # per-chunk walls instead of under-charging one instant
        retraces0 = monitor.retrace_count()
        t0 = flight_recorder.now_ns()
        try:
            if st["next"] < st["n"] - 1:
                self._chunk_step(st)
            else:
                self._finish_chunked(st)
        except Exception as e:
            self._abort_chunked(
                f"admission error: {type(e).__name__}: {e}",
                label="error")
            monitor.record_swallowed("serving.admit", e)
        finally:
            self._charge_admission(
                req, (flight_recorder.now_ns() - t0) * 1e-9,
                monitor.retrace_count() > retraces0)
            # the blocking chunk sync must not be attributed to
            # per-token decode latency: re-anchor the poll window
            # (the same artifact class as inline admission)
            self._window_steps = 0

    def _chunk_step(self, st: dict):
        """One non-final chunk: side-cache forward, paged span install,
        blocking sync, telemetry."""
        req, slot, k = st["req"], st["slot"], st["next"]
        C = self.prefill_chunk_tokens
        t_ns = flight_recorder.now_ns() if req.traced else 0
        ids = jnp.asarray(st["ids"][:, k * C:(k + 1) * C])
        self._row_cache = self._run(
            ("chunk", C), self._state, ids, self._row_cache)
        if self._alloc is not None:
            # commit the chunk's positions into the planned pages now —
            # only the span at/past the shared prefix (and past already
            # installed chunks) is written; the table/kv_len install
            # waits for the final admit
            start = max(k * C, st["shared"])
            if (k + 1) * C > start:
                self._cache = self._run(
                    ("install_span",), self._cache, self._row_cache,
                    self._table_row(self._pending_pages[req.id][0]),
                    np.int32(start))
        # the chunk must LAND before the host moves on: the sync point
        # is what bounds how long a chunk can monopolize the device
        # between decode dispatches
        _, t1 = self._sync("chunk", self._row_cache.kv_len.block_until_ready)
        st["next"] = k + 1
        self._chunk_landed(st, k, t_ns, t1)

    def _chunk_landed(self, st: dict, k: int, t_ns: int, t1: int):
        """Chunk ``k`` (the final one too) is in the side cache: count
        and record it."""
        req, slot, C = st["req"], st["slot"], self.prefill_chunk_tokens
        tokens = min(C, st["plen"] - k * C)
        self.stats["prefill_chunks"] += 1
        monitor.record_prefill_chunk(tokens)
        if flight_recorder.enabled:
            flight_recorder.record(
                "serve.prefill_chunk", req=req.id, slot=slot, chunk=k,
                start=k * C, tokens=tokens, remaining=st["n"] - k - 1)
        req.span("prefill_chunk", t_ns, t1, chunk=k, slot=slot,
                 tokens=tokens)

    def _finish_chunked(self, st: dict):
        """The final (padded) chunk + admission: sample the first token
        (TTFT), install the side cache into the slot through the
        ordinary admit program, flip the request to RUNNING, rebuild
        the (donated) side cache for the next chunked admission."""
        req, slot, k = st["req"], st["slot"], st["n"] - 1
        C = self.prefill_chunk_tokens
        t_ns = flight_recorder.now_ns() if req.traced else 0
        ids = jnp.asarray(st["ids"][:, k * C:(k + 1) * C])
        plen = jnp.asarray(np.array([st["plen"]], np.int32))
        tok, row_cache, self._key, fin = self._run(
            ("chunk_final", C),
            self._state, ids, plen, self._key, self._row_cache)
        self._row_cache = row_cache
        # TTFT measurement point — same contract as inline admission
        _, t1 = self._sync("prefill", tok.block_until_ready)
        self._first_token(req, st["t_ns"], t1, st["n"] * C)
        self._chunk_landed(st, k, t_ns, t1)
        monitor.record_prefill_interleave(
            st["decode_steps"] / st["n"])
        # every span below the last chunk boundary is already
        # installed: the admit's install_row writes only the final
        # span (start = the later of shared prefix end and the final
        # chunk's base)
        self._install(req, slot, row_cache, tok, fin, installed=k * C)
        self._chunking = None
        # the admit program donated the side cache: rebuild it zeroed
        # (kv_len 0, clips 0) so the next chunked admission starts
        # clean — this rebuild IS the between-requests reset
        self._row_cache = _host_zeros(self._row_cache_aval)

    def _abort_chunked(self, reason: str, label: Optional[str] = None):
        """Terminal exit for a mid-prefill request (deadline, drain,
        dispatch error): release its committed pages, clear the slot,
        rebuild the side cache. No free-program dispatch — the device
        lane was never installed (finished True, kv_len 0, null
        table), so there is nothing to reset."""
        st, self._chunking = self._chunking, None
        if st is None:
            return
        req, slot = st["req"], st["slot"]
        if flight_recorder.enabled:
            flight_recorder.record(
                "serve.evict", req=req.id, slot=slot, reason=reason,
                tokens=0, chunks_done=st["next"])
        self._release_pending(req)
        self._slots[slot] = None  # lint: lock-discipline-ok (abort runs under the caller's pump lock)
        # the side cache holds the aborted prompt's partial prefix —
        # rebuild zeroed before the next chunked admission
        self._row_cache = _host_zeros(self._row_cache_aval)
        self._cancel(req, reason, label=label)
        self._note_cost(req)

    def _dispatch_decode(self):
        exe = self._compiled(self._mode.key)
        # (every step mode's decode step is program "step")
        with flight_recorder.span("serve.dispatch", program="step") as sp:
            self._cache, self._lanes, self._key = exe(
                self._state, self._cache, self._lanes, self._key)
        self._seq += 1
        self._steps_since_poll += 1
        if self._chunking is not None:
            # decode steps interleaved into THIS chunked admission —
            # the serve.prefill.interleave_ratio numerator
            self._chunking["decode_steps"] += 1
        if self._window_steps == 0:
            # anchor the latency window at the first dispatch after a
            # poll — idle gaps between traffic bursts must not be
            # attributed to per-token latency
            self._window_t0_ns = sp.end_ns or flight_recorder.now_ns()
        self._window_steps += 1
        self.stats["decode_steps"] += 1
        monitor.record_generation(decode_steps=1)

    def _poll_ahead(self) -> bool:
        """Whether a poll dispatches the next decode step before its
        read: when a request that arrives during the read could not be
        admitted at its return anyway — no slot is free, or the queue's
        head waits for pages. The chain behind the read (completions,
        the generator, the next admission's planning and dispatch) then
        runs while that step does, and a lane the poll frees sits out
        exactly that one step, masked. With a slot free and nothing
        blocked the read has nothing behind it, so an arrival is
        prefilled the moment it returns and its TTFT pays no step."""
        return (self._alloc is not None and self._page_blocked) \
            or all(s is not None for s in self._slots)

    def _poll(self):
        """Scheduler poll: read the [batch] finished/step lanes (the
        only per-window host sync on the decode path), complete
        finished rows, cancel over-deadline ones, time the window."""
        with flight_recorder.span("serve.poll") as sp:
            self._poll_lanes(sp)

    def _poll_lanes(self, sp):
        covered, self._steps_since_poll = self._steps_since_poll, 0
        t_window, n_window = self._window_t0_ns, self._window_steps
        self._window_steps = 0   # next dispatch re-anchors the window
        # the [batch] finished/step lanes, the mode's on-device counters,
        # the cache's kv_len and a quantized cache's counter (a few
        # int32, in the same read), and the result rows for the lanes
        # found finished
        view = programs.poll_view(self._mode, self._cache, self._lanes)
        if self._poll_ahead():
            # AT MOST one step ahead of a poll's read. The step donates
            # the lanes, so the read is of copies made in between
            view = self._run(("poll_view",), view)
            after = self._mark()
            self._dispatch_decode()
        else:
            after = self._mark()
        seen, t_ns = self._sync(
            "poll", lambda: jax.device_get(view._replace(rows=())), after)  # lint: host-sync-ok (scheduler poll, every poll_every steps)
        fin, steps = seen.finished, seen.steps
        if self._window_steps:
            # the step in flight opens the next window where this read
            # returned: windows neither overlap nor leave a gap
            self._window_t0_ns = t_ns
        rows = None

        def row(i):
            """Lane ``i``'s result rows: every lane's, behind ONE wait
            a poll, and never behind the step in flight."""
            nonlocal rows
            if rows is None:
                rows, _ = self._sync(
                    "row", lambda: jax.device_get(view.rows), after)  # lint: host-sync-ok (one row read per completing poll)
            return tuple(r[i].copy() for r in rows)

        drained = self._mode.drain(seen.counters, self.stats)
        now = t_ns * 1e-9
        with flight_recorder.span("serve.telemetry"):
            self._charge_window(t_ns, t_window, n_window)
        emitted = admitted = completed = evicted = 0
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            if req.status is RequestStatus.PENDING_PREFILL:
                # mid-chunked-prefill: the lane is parked (its finished
                # flag reads True) — completion/deadline/trace handling
                # belongs to _advance_chunked, not the decode poll
                continue
            # the lane's progress since the last poll saw it (a lane
            # polled for the first time brings its prefill's token)
            n = int(steps[i])
            admitted += req.n_emitted == 0
            emitted += n - req.n_emitted
            req.n_emitted = n
            if fin[i]:
                self._complete(req, self._mode.cut(req, row(i), n, False))
                completed += 1
                # freed in place; the next admission overwrites the row
                self._slots[i] = None  # lint: lock-discipline-ok (poll runs under the caller's pump lock)
                self._free_slot_pages(i)
            elif req.deadline is not None and now > req.deadline:
                # the rows are read BEFORE the free program: it donates
                # the lanes a poll that did not run ahead reads them from
                self._evict(i, req, "deadline", n, row(i))
                evicted += 1
            elif req.traced:
                # rolling decode segment: one span per poll window, so
                # a mid-flight dump shows how far the request got
                req.span("decode", req._t_seg_ns, t_ns, tokens=n)
                req._t_seg_ns = t_ns
        self.stats["emitted_tokens"] += emitted
        self.stats["polls"] += 1
        sp.set(steps=covered, emitted=emitted, admitted=admitted,
               completed=completed, evicted=evicted,
               live=sum(s is not None for s in self._slots), **drained)
        # expire queued requests that can no longer meet their deadline
        with self._qlock:
            for req in list(self._queue):
                if req.deadline is not None and now > req.deadline:
                    self._queue.remove(req)
                    self._unblock_if(req)
                    self._cancel(req, "deadline")
            monitor.record_serve_queue_depth(len(self._queue))
        with flight_recorder.span("serve.telemetry"):
            monitor.record_serve_slot_occupancy(
                sum(s is not None for s in self._slots) / self.max_batch)
            if monitor.enabled:
                # the cache's own occupancy() reads kv_len off the
                # device: behind the step in flight, were it called here
                monitor.record_cache_occupancy(
                    float(seen.kv_len.max()) / self._cache.max_len)  # lint: host-sync-ok (host array)
                self._drain_page_stats()
                self._drain_quant_stats(seen.clips)
                self._goodput.flush()
                # SLO watchtower: sample the time-series ring + evaluate
                # burn rates at most once per ring period (fast path is
                # a float compare — gated in test_overhead_gate)
                slo_mod.tick()

    def _charge_window(self, t_ns: int, t_window: Optional[int],
                       n_window: int):
        """Book the decode window a poll's read just closed, BEFORE the
        poll completes anything (a request finishing this window still
        pays for it): per-token latency, goodput compute, and each live
        request's share. Telemetry: the scheduler reads none of it."""
        if t_window is None or not n_window:
            return
        window_dt = (t_ns - t_window) * 1e-9
        monitor.record_serve_token_latency(window_dt / n_window)
        # the dispatch window (host dispatches + the device wait the
        # lane reads just paid) is goodput compute
        self._goodput.charge("compute", window_dt)
        if window_dt > 0.0:
            # cost attribution: every live request owns an equal share
            # of the window the ledger just booked as compute (shares
            # sum to the window — Request.cost() reconciles against
            # the compute bucket), plus page*seconds for its resident
            # KV pages. Charged BEFORE completions below, so a request
            # finishing this window still pays for it.
            # PENDING_PREFILL slots are NOT in the decode window: the
            # chunk walls charge to prefill_s in _advance_chunked —
            # charging a share here would double-bill the request
            live = sum(r is not None
                       and r.status is not RequestStatus.PENDING_PREFILL
                       for r in self._slots)
            if live:
                share = window_dt / live
                for i, r in enumerate(self._slots):
                    if r is None or \
                            r.status is RequestStatus.PENDING_PREFILL:
                        continue
                    r._cost_decode_s += share
                    if self._alloc is not None:
                        pages = self._row_pages[i]
                        if pages:
                            r._cost_page_s += len(pages) * window_dt

    def _read_row(self, slot: int):
        """One lane's result rows (the mode's ``row`` lanes, in position
        order), all behind ONE wait, from the lanes as they stand: for
        an eviction outside a poll (a poll reads its view's)."""
        return jax.device_get(tuple(  # lint: host-sync-ok (one row read per eviction at the drain's cutoff)
            getattr(self._lanes, n)[slot] for n in self._mode.row))

    def _complete(self, req: Request, toks: np.ndarray):
        eos = self._cfg.eos_token_id
        req.n_emitted = int(toks.size)
        n_real = int(toks.size)
        if eos is not None:
            hits = np.nonzero(toks == eos)[0]
            if hits.size:
                n_real = int(hits[0]) + 1    # the eos itself counts
                toks = toks[:int(hits[0])]   # result is eos-trimmed
        req.tokens = toks.astype(np.int32)
        monitor.record_generation(tokens=n_real)
        req._finish(RequestStatus.COMPLETED)
        self.stats["completed"] += 1
        monitor.record_serve_request("completed")
        self._note_cost(req)

    def _cancel(self, req: Request, reason: str,
                label: Optional[str] = None):
        """Terminal CANCELLED for a request not occupying a slot.
        ``label`` overrides the metric label when ``reason`` carries
        free text (error messages must not become label cardinality)."""
        req._finish(RequestStatus.CANCELLED, reason)
        self.stats["cancelled"] += 1
        monitor.record_serve_request("cancelled")
        monitor.record_serve_cancellation(label or reason)

    def _evict(self, slot: int, req: Request, reason: str,
               n_done: int = 0, row=None, label: Optional[str] = None):
        """Cancel an in-flight request: mask its lane + reset its cache
        row via the free program, keep whatever it produced (``row``:
        its result rows as the poll that evicts it read them; ``label``
        as ``_cancel``'s)."""
        if flight_recorder.enabled:
            flight_recorder.record("serve.evict", req=req.id, slot=slot,
                                   reason=reason, tokens=n_done)
        self._cache, self._lanes = self._run(
            ("free",), self._cache, self._lanes, np.int32(slot))
        if n_done:
            if row is None:
                row, _ = self._sync("row", lambda: self._read_row(slot))
            req.tokens = self._mode.cut(req, row, n_done, True) \
                .astype(np.int32)
            req.n_emitted = int(req.tokens.size)
        self._slots[slot] = None  # lint: lock-discipline-ok (eviction runs under the caller's pump lock)
        self._free_slot_pages(slot)
        self._cancel(req, reason, label=label)
        self._note_cost(req)

    def _note_cost(self, req: Request):
        """Terminal cost attribution: land the request's accumulated
        cost in the serve.cost.* histograms and keep the top-K most
        expensive requests for the /slo table."""
        c = req.cost()
        monitor.record_request_cost(c["prefill_s"], c["decode_s"],
                                    c["page_s"])
        with self._qlock:
            heapq.heappush(self._cost_top, (c["total_s"], req.id, c))
            while len(self._cost_top) > self._cost_topk:
                heapq.heappop(self._cost_top)

    def cost_table(self) -> List[dict]:
        """The top-K most expensive terminal requests, costliest
        first — the /slo endpoint's per-request attribution table."""
        with self._qlock:
            top = sorted(self._cost_top, reverse=True)
        return [dict(req=rid, **{k: round(v, 6) for k, v in c.items()})
                for _, rid, c in top]

    # ------------------------------------------------- page bookkeeping
    def _free_slot_pages(self, slot: int):
        """Return a terminal slot's page references to the allocator
        (pages referenced by other rows or cached in the prefix
        registry stay resident — that is the sharing)."""
        if self._alloc is None:
            return
        pages, self._row_pages[slot] = self._row_pages[slot], None
        if pages:
            self._alloc.free_row(pages)

    def _release_pending(self, req: Request):
        """Roll back a committed page plan whose admission failed."""
        if self._alloc is None:
            return
        ent = self._pending_pages.pop(req.id, None)
        if ent is not None:
            self._alloc.free_row(ent[0])

    def _drain_page_stats(self):
        """Forward the allocator's lifetime counters into the metrics
        registry as deltas (called at the poll cadence — host ints
        only, no device sync)."""
        if self._alloc is None:
            return
        stats = dict(self._alloc.stats)
        prev, self._page_seen = self._page_seen, stats
        delta = {k: stats[k] - prev.get(k, 0) for k in stats}
        monitor.record_paged_cache(
            allocated=delta["pages_allocated"],
            freed=delta["pages_freed"],
            prefix_hits=delta["prefix_hits"],
            shared_pages=delta["shared_pages"],
            cow_copies=delta["cow_copies"])
        monitor.record_page_occupancy(self._alloc.page_occupancy())

    def _drain_quant_stats(self, clips=None):
        """Drain the quantized cache's in-device saturation counter
        into ``gen.cache.quant.scale_clips`` (one int32 scalar, which a
        poll reads with its lanes and hands in; read here at the
        drain's end; the lifetime counter is int32 and may wrap —
        modular delta, same treatment as the speculation counters)."""
        if getattr(self._cache, "clips", None) is None:
            return
        if clips is None:
            clips, _ = self._sync(
                "stats", lambda: np.asarray(self._cache.clips))  # lint: host-sync-ok (drain's end, tiny scalar)
        clips = int(clips)
        d = (clips - self._clips_seen) % (1 << 32)
        if d:
            self._clips_seen = clips
            monitor.record_kv_quant(scale_clips=d)

    # -------------------------------------------------------- front-end
    def _submit_item(self, item) -> Request:
        if isinstance(item, tuple) and len(item) == 2 and \
                isinstance(item[1], RequestParams):
            return self.submit(item[0], item[1])
        return self.submit(item)

    def serve_forever(self, request_iter=None, *, shutdown=None,
                      on_step=None, idle_sleep_s: float = 0.0005):
        """Blocking serve loop. With ``request_iter`` it pulls prompts
        (or ``(prompt, RequestParams)`` tuples; the iterator must not
        block in ``__next__``) whenever the queue has room and returns
        the submitted handles once the iterator is exhausted and every
        request is terminal. With ``request_iter=None`` it really does
        serve forever — pumping ``submit()`` traffic from other threads
        through idle gaps — until a preemption or ``shutdown()`` ends
        it.

        Preemption: when the active ``GracefulShutdown`` context (or
        ``shutdown``) reports preempted — or ``shutdown()`` was called —
        the loop drains: queued requests get a clean REJECTED, in-flight
        slots keep decoding up to ``drain_timeout_s`` then are cancelled;
        nothing hangs. ``on_step(engine)`` runs once per loop iteration
        (traffic shaping, fault injection in tests)."""
        from ..distributed import resilience
        handles: List[Request] = []
        it = iter(request_iter) if request_iter is not None else None
        exhausted = False   # an iterator-less loop never "finishes"
        try:
            while True:
                gs = shutdown if shutdown is not None \
                    else resilience.active()
                if self._shutdown or (gs is not None and gs.preempted):
                    preempted_drain = gs is not None and \
                        gs.preempted and not self._shutdown
                    if preempted_drain:
                        # preemption landed mid-serve: leave the black
                        # box BEFORE draining, while the in-flight
                        # requests' spans still show what was running
                        flight_recorder.record(
                            "serve.preempted",
                            in_flight=sum(s is not None
                                          for s in self._slots))
                        flight_recorder.auto_dump("preemption")
                    compute0 = self._goodput.bucket_total("compute")
                    t_drain = flight_recorder.now_ns()
                    self.drain()
                    if preempted_drain:
                        # the preemption-recovery bucket gets the drain
                        # wall MINUS the decode windows that already
                        # charged compute inside it (no second count)
                        dc = self._goodput.bucket_total("compute") \
                            - compute0
                        self._goodput.charge(
                            "preemption_recovery",
                            max((flight_recorder.now_ns() - t_drain)
                                * 1e-9 - dc, 0.0))
                    break
                while it is not None and not exhausted and \
                        self._queue_room():
                    try:
                        item = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    handles.append(self._submit_item(item))
                if on_step is not None:
                    on_step(self)
                if self.busy:
                    self.step()
                elif exhausted:
                    break
                else:
                    time.sleep(idle_sleep_s)
        except BaseException as e:
            # an uncaught scheduler/device error — or an operator's
            # Ctrl-C — is exactly when the flight recorder earns its
            # keep: dump, then propagate (same contract as fit();
            # SystemExit means a preemption path that already dumped)
            if not isinstance(e, SystemExit):
                flight_recorder.record(
                    "serve.crash", error=f"{type(e).__name__}: {e}")
                flight_recorder.auto_dump("serve_crash")
            raise
        return handles

    def drain(self):
        """Graceful shutdown: reject everything still queued, keep
        decoding in-flight slots until each reaches a terminal status
        or ``drain_timeout_s``, then cancel the stragglers. Every
        request ends terminal; none hang. Idempotent; the engine
        accepts no new work afterwards."""
        with self._pump_lock:
            with self._qlock:
                already = self._shutdown and not self._queue \
                    and all(s is None for s in self._slots)
                self._shutdown = True
                queued, self._queue = \
                    list(self._queue), collections.deque()
                if self._alloc is not None:
                    self._blocked_key = None
                    self._page_blocked = False
                monitor.record_serve_queue_depth(0)
            if flight_recorder.enabled and not already:
                flight_recorder.record(
                    "serve.drain_begin", queued=len(queued),
                    in_flight=sum(s is not None for s in self._slots))
            for req in queued:
                req._finish(RequestStatus.REJECTED, "shutdown")
                self.stats["rejected"] += 1
                monitor.record_serve_request("rejected")
            # a PENDING_PREFILL slot can never decode to terminal —
            # abort it NOW (pages back to the free list, request
            # CANCELLED) or the decode drain below would spin on its
            # occupied slot until the timeout
            self._abort_chunked("shutdown")
            deadline = time.monotonic() + self.drain_timeout_s
            while any(s is not None for s in self._slots) and \
                    time.monotonic() < deadline:
                self._dispatch_decode()
                if self._steps_since_poll >= self.poll_every:
                    self._poll()
            if any(s is not None for s in self._slots):
                # final poll before declaring stragglers: rows that
                # finished since the last cadence poll must complete,
                # not get mislabeled CANCELLED
                self._poll()
            steps = np.asarray(self._steps)  # lint: host-sync-ok (drain-cutoff lane read)
            for i, req in enumerate(self._slots):
                if req is not None:
                    self._evict(i, req, "shutdown", int(steps[i]))
            monitor.record_serve_slot_occupancy(0.0)
            if monitor.enabled:
                self._drain_page_stats()
                self._drain_quant_stats()
                self._goodput.flush()
            if flight_recorder.enabled and not already:
                flight_recorder.record("serve.drain_end")
            if self.fleet is not None and not already:
                # push the final counters so the aggregator's last view
                # of this replica is the drained one (thread keeps
                # running — /fleet staleness only starts at shutdown)
                try:
                    self.fleet.publisher.publish_now()
                except Exception as e:
                    monitor.record_swallowed("serving.fleet_drain", e)

    shutdown_now = drain

    # ----------------------------------------------------- thread mode
    def start(self) -> "ServingEngine":
        """Background pump thread: ``submit()``/``result()`` from any
        thread, ``shutdown()`` to drain and stop."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._thread = threading.Thread(
            target=self._run_loop, daemon=True, name="serving-engine")
        self._thread.start()
        return self

    def _run_loop(self):
        while not self._shutdown:
            if self.busy:
                with self._pump_lock:
                    if not self._shutdown:
                        self.step()
            else:
                time.sleep(0.001)

    def shutdown(self):
        """Drain (every request terminal), stop the pump thread, and
        release the telemetry port. drain() alone deliberately keeps
        the server up — a post-drain scrape is how the fleet observes
        the exit — but full shutdown() must free the port so a
        relaunched engine on the same fixed port can bind."""
        self.drain()
        if self._thread is not None:
            self._thread.join(timeout=self.drain_timeout_s + 5.0)
            self._thread = None
        self._goodput.close()
        if self.fleet is not None:
            self.fleet.stop()   # final publish rides in stop()
            self.fleet = None
        if self.telemetry is not None:
            self.telemetry.stop()
            self.telemetry = None

    def _try_pump(self) -> bool:
        """Inline pump for handle.result() when no thread owns the
        engine; returns True when it made progress."""
        if self._thread is not None and self._thread.is_alive():
            return False
        if not self._pump_lock.acquire(blocking=False):
            return False
        try:
            if self.busy and not self._shutdown:
                self.step()
                return True
            return False
        finally:
            self._pump_lock.release()

    def goodput(self) -> Dict:
        """The serve-side goodput decomposition right now:
        ``{"wall_s", "buckets", "goodput_fraction"}`` with every
        bucket summing to wall time (bench's ``"goodput"`` sub-dict,
        and the tier-1 ledger-invariant gate)."""
        return self._goodput.snapshot()

    # ----------------------------------------------------------- health
    def health(self) -> Dict:
        """Readiness snapshot for the telemetry server's ``/readyz``:
        ready iff warm (every program compiled/loaded), not draining/
        shut down, and the queue is below its bound — the backpressure
        signal a multi-replica router needs to stop sending traffic
        BEFORE submits start raising QueueFull. Always includes the
        capacity detail (queue depth, slot occupancy) so a 503 is
        self-explaining."""
        with self._qlock:
            depth = len(self._queue)
        busy = sum(s is not None for s in self._slots)
        paged = self._alloc is not None
        # what the queue head is actually waiting on: "pages" = the
        # pool could not cover its plan (MEMORY pressure — more HBM or
        # fewer/shorter requests would help), "slots" = every decode
        # lane is busy (ADMISSION capacity — another replica would
        # help). The distinction is what the multi-replica router
        # routes on; it also suffixes the 503 reason below.
        blocked_on = None
        if depth:
            if paged and self._page_blocked:
                blocked_on = "pages"
            elif busy >= self.max_batch:
                blocked_on = "slots"
        reasons = []
        if self._shutdown:
            reasons.append("draining")
        if not self._warm:
            reasons.append("warming")
        if depth >= self.max_queue:
            # suffix the blocker only when it is actually known — a
            # submit burst can fill the queue between scheduler steps
            # while slots are still free
            reasons.append("queue_full" if blocked_on is None
                           else f"queue_full:no_free_{blocked_on}")
        # effective cache capacity in TOKENS (PR-12's named remainder):
        # pool pages x page size for the paged cache, slots x max_len
        # dense — REAL headroom, already adjusted for the cache dtype
        # because an int8 pool configured at equal HBM holds ~2x the
        # pages/slots of a bf16 one. The kv_dtype label rides along so
        # the item-1 router can compare replicas across precisions.
        if paged:
            cap_tokens = (self._alloc.n_pages - 1) * self.page_size
            free_tokens = self._alloc.free_pages() * self.page_size
        else:
            cap_tokens = self.max_batch * self.max_len
            free_tokens = (self.max_batch - busy) * self.max_len
        # prefill backlog (chunked admission in flight): prompt tokens
        # not yet written to the KV cache + chunks still to run. The
        # fleet router folds this into its score so long prompts steer
        # away from a replica that is mid-prefill — its next chunks
        # will keep taxing every decode window it serves.
        pp_tokens = pp_chunks = 0
        st = self._chunking
        if st is not None:
            pp_tokens = max(
                0, st["plen"] - st["next"] * self.prefill_chunk_tokens)
            pp_chunks = st["n"] - st["next"]
        return {
            "ready": not reasons,
            **({"reason": ",".join(reasons)} if reasons else {}),
            "queue_depth": depth, "max_queue": self.max_queue,
            "queue_blocked_on": blocked_on,
            "slots_busy": busy, "max_batch": self.max_batch,
            "free_slots": self.max_batch - busy,
            "kv_cache_dtype": self._kv_dtype_label,
            "capacity_tokens": cap_tokens,
            "free_tokens": free_tokens,
            "pending_prefill_tokens": pp_tokens,
            "prefill_chunks_queued": pp_chunks,
            **({"free_pages": self._alloc.free_pages(),
                "total_pages": self._alloc.n_pages - 1,
                "page_occupancy": round(
                    self._alloc.page_occupancy(), 4)} if paged else {}),
            # static HBM plan (computed when a budget gates the engine,
            # or on the first memory_plan() call): the router can admit
            # on PREDICTED headroom instead of discovering an OOM
            **({"predicted_peak_bytes":
                    self._mem_summary["predicted_peak_bytes"],
                **({"hbm_budget": self.hbm_budget,
                    "predicted_headroom_bytes":
                        self.hbm_budget
                        - self._mem_summary["predicted_peak_bytes"]}
                   if self.hbm_budget is not None else {})}
               if self._mem_summary is not None else {}),
            "warm": self._warm, "draining": self._shutdown,
        }

    # ---------------------------------------------------- memory plan
    def memory_plan(self) -> Dict:
        """Predicted HBM footprint of this engine, from the static
        planner (``analysis.plan_memory`` — trace-only, nothing
        executes): the decode program's peak at the TPU donation
        intent (weights + kv cache + lanes resident, in-place via
        donation) and the admission transient (a batch-1 prefill at
        the largest bucket runs WHILE the engine state is resident —
        its peak minus the shared weights rides on top). Returns the
        byte breakdown plus the two :class:`analysis.MemoryPlan`\\ s;
        cached after the first call. The constructor validates this
        against ``hbm_budget`` and ``health()`` exports the headroom."""
        if self._mem_summary is not None:
            return self._mem_summary
        self._ensure_eval()
        step = self._programs[self._mode.key]
        decode = step.plan("serving.decode")
        prefill = self._programs[("prefill", self.buckets[-1])]
        prefill = prefill.plan("serving." + prefill.name)
        # the chunk program's transient rides on top of the SAME
        # resident engine state as an inline admission — plus it
        # keeps the side cache resident between chunks (an operand
        # of the plan, so its bytes are inside chunk.peak_bytes)
        chunk = self._programs.get(("chunk", self.prefill_chunk_tokens))
        if chunk is not None:
            chunk = chunk.plan("serving." + chunk.name)
        if decode.arg_bytes is not None:
            operands = list(step.args)
            weights = decode.arg_bytes[operands.index("state")]
            kv = decode.arg_bytes[operands.index("cache")]
            lanes = sum(decode.arg_bytes) - weights - kv
            # an admission's transient shares the resident weights
            resident = sum(decode.arg_bytes) - weights
        else:
            # exotic-pytree fail-safe (audit couldn't line leaves up
            # with positional args): no per-operand breakdown, and the
            # prefill transient can't subtract the shared weights —
            # predict CONSERVATIVELY rather than crash or under-gate
            weights = kv = lanes = None
            resident = decode.args_bytes
        predicted = max(decode.peak_bytes, *(
            resident + p.peak_bytes for p in (prefill, chunk)
            if p is not None))
        self._mem_summary = {
            "weights_bytes": weights, "kv_cache_bytes": kv,
            "lanes_bytes": lanes,
            "decode_peak_bytes": decode.peak_bytes,
            "prefill_peak_bytes": prefill.peak_bytes,
            **({"chunk_peak_bytes": chunk.peak_bytes}
               if chunk is not None else {}),
            "predicted_peak_bytes": predicted,
            "plans": {"decode": decode, "prefill": prefill,
                      **({"chunk": chunk} if chunk is not None else {})},
        }
        return self._mem_summary

    # ------------------------------------------------------------ audit
    def audit(self, **audit_kw) -> Dict:
        """Static audit of every program the scheduler dispatches: one
        prefill report per bucket plus the decode/admit/free trio
        (analysis.audit over abstract operands — nothing executes).
        The slot-decode and admit programs are audited with the TPU
        donation INTENT (KV cache + every token/flag lane donated) even
        on CPU; the tier-1 gate asserts zero ERROR findings everywhere
        and donation coverage 1.0 on the slot-decode program — the
        cache and token buffers must stay in-place across scheduler
        steps."""
        # audit must describe the EVAL program the engine serves, even
        # when called mid-fit on a shared layer
        self._ensure_eval()
        base = audit_kw.pop("name", "serving")
        return {prog.report: prog.audit(f"{base}.{prog.name}", **audit_kw)
                for prog in self._programs.values()}

    def __repr__(self):
        occ = sum(s is not None for s in self._slots)
        with self._qlock:
            q = len(self._queue)
        paged = "" if self._alloc is None else \
            (f", pages={self._alloc.used_pages()}"
             f"/{self._alloc.n_pages - 1}x{self.page_size}")
        return (f"ServingEngine(slots={occ}/{self.max_batch}, "
                f"queued={q}, buckets={self.buckets}, "
                f"cache_len={self.max_len}{paged}, "
                f"warm={self._warm}, shutdown={self._shutdown})")

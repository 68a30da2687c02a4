"""The serving engine's device programs, each written down once.

``engine.py`` schedules (queue, slots, pages, chunked-prefill state,
poll, drain, health, cost); what runs on the device is here.

- :class:`Program` is one program's record: its key in the engine's
  executable table and the executable store, the function, its operands
  by NAME in call order, which are static and which donated. ``warmup()``
  lowers the record, ``audit()`` audits it, ``memory_plan()`` plans it;
  positions are worked out here and nowhere else.
- A step mode (:class:`Decode`, :class:`Speculative`,
  :class:`BlockDiffusion`) is all that differs between the three ways
  the fixed-batch step advances its lanes. The engine chooses one in
  ``__init__`` and never asks which it holds. Not an extension point:
  three classes, no registry.
- Every step is ``(state, cache, lanes, key) -> (cache, lanes, key)``,
  the mode's config static, all three donated. One ``admit_fn`` serves
  every mode and cache: the cache takes the row (``install_row``), the
  mode writes the lanes.

A device trace names a program ``jit_<function name>``, and the benchmark
finds ``jit_step_fn``, ``jit_block_step_fn`` and ``jit_prefill_fn`` by
PREFIX: keep those names, and start no other jitted name with them.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import types
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monitor
from ..core.tensor import Tensor
from ..generation.api import _expect_logits_cache, _sample_cfg
from ..generation.block_diffusion import apply_block_step, first_block
from ..generation.sampling import sample
from ..generation.speculative import apply_verify_window, ngram_propose

__all__ = ["Program", "Network", "Decode", "Speculative", "BlockDiffusion",
           "poll_view", "program_table"]

_sds = jax.ShapeDtypeStruct
_SCALAR = _sds((), jnp.int32)


def _avals(tree):
    return jax.tree_util.tree_map(
        lambda a: _sds(tuple(a.shape), a.dtype), tree)


# ----------------------------------------------------------- the record

@dataclasses.dataclass
class Program:
    """One device program, stated once.

    ``args`` maps each operand's name to its aval, live buffer or static
    value, in call order; a value may be a thunk where its aval costs a
    trace (the admit's prefill row), so that a warm relaunch, which
    needs the names but lowers nothing, never pays it. ``static`` and
    ``donates`` name operands. ``name`` is the program's audit and plan
    name under the engine's, ``report`` its key in ``audit()``'s dict
    (the ``key`` itself unless given)."""
    key: tuple
    fn: Callable
    args: Dict[str, Any]
    static: Tuple[str, ...] = ()
    donates: Tuple[str, ...] = ()
    name: str = ""
    report: Any = None

    def __post_init__(self):
        if self.report is None:
            self.report = self.key

    def _argnums(self, names) -> Tuple[int, ...]:
        return tuple(i for i, n in enumerate(self.args) if n in names)

    @property
    def donation_intent(self) -> Tuple[int, ...]:
        """The TPU donation design whatever the running backend:
        ``audit()`` and ``memory_plan()`` gate against it."""
        return self._argnums(self.donates)

    @property
    def donation(self) -> Tuple[int, ...]:
        """What the jit donates here: on TPU only (CPU/GPU donation is a
        no-op that warns once per program)."""
        return self.donation_intent \
            if jax.default_backend() == "tpu" else ()

    def operands(self) -> tuple:
        return tuple(v() if isinstance(v, types.FunctionType) else v
                     for v in self.args.values())

    @functools.cached_property
    def jit(self):
        return jax.jit(self.fn, static_argnums=self._argnums(self.static),
                       donate_argnums=self.donation)

    def lower(self):
        return self.jit.lower(*self.operands())

    def audit(self, name: str, **audit_kw):
        from ..analysis import audit
        return audit(self.fn, *self.operands(),
                     static_argnums=self._argnums(self.static),
                     donate=self.donation_intent, name=name, **audit_kw)

    def plan(self, name: str):
        """The audit's memory pass alone (``analysis.plan_memory``)."""
        return self.audit(name, checks=("memory",)).memory


def _bind(fn, first):
    """``fn`` with its first argument bound, under ``fn``'s own name: the
    name is what a device trace shows, and a ``partial`` has none. Bound
    per engine, so jit's caches let go of the network with it."""
    def program(*args):
        return fn(first, *args)
    program.__name__ = program.__qualname__ = fn.__name__
    return program


class Network:
    """The served layer as a function of its weights: what every program
    body calls. ``cache_kw`` is the prefill's cache dtype, ``block_kw``
    the mode's ``forward_kw``: the block length of a block-causal
    forward (empty: causal)."""

    def __init__(self, layer, sp, cache_kw, block_kw):
        self.layer, self.sp = layer, sp
        self.cache_kw, self.block_kw = cache_kw, block_kw

    def __call__(self, state_vals, ids, **kw):
        from ..jit.api import _unwrap, functional_call
        params = self.sp.materialize(state_vals)
        out = functional_call(self.layer, dict(zip(self.sp.names, params)),
                              Tensor(ids), **kw)
        logits, cache = _expect_logits_cache(out)
        return _unwrap(logits), cache


# ------------------------------------------------------ program bodies

def _first_token(logits, cache, key, cfg):
    """The (tok, cache, key, finished) a prefill hands the admit."""
    logits = logits[:, -1].astype(jnp.float32)
    k0, k1 = jax.random.split(key)
    tok = sample(logits, k0, **_sample_cfg(cfg))
    if cfg.eos_token_id is not None:
        finished = tok == cfg.eos_token_id
    else:
        finished = jnp.zeros(tok.shape, bool)
    return tok, cache, k1, finished


def prefill_fn(net, state_vals, ids, plen, key, cfg, cache_len):
    logits, cache = net(state_vals, ids, use_cache=True, prompt_len=plen,
                        cache_max_len=cache_len, **net.cache_kw,
                        **net.block_kw)
    if net.block_kw:
        # the prefill commits the prompt's whole blocks and samples
        # nothing (logits predict the token AT a position): the head
        # falls out of the program
        none = jnp.zeros((ids.shape[0],), jnp.int32)
        return none, cache, key, none.astype(bool)
    return _first_token(logits, cache, key, cfg)


def _count_routing(net, moe_counters):
    """``moe_counters`` plus this forward's routing through the layer's
    dropless expert layers: rows computed, the busiest expert's and the
    rows sent to experts held elsewhere, summed over layers (unchanged
    where the layer has none)."""
    from ..distributed.parallel.moe import routing_stats
    moe = routing_stats(net.layer)
    if moe is None:
        return moe_counters
    return moe_counters + jnp.stack(moe).astype(jnp.int32)


def step_fn(net, state_vals, cache, lanes, key, cfg):
    tok, finished, steps, budget, out_buf, moe_counters = lanes
    logits, cache = net(state_vals, tok[:, None], cache=cache)
    logits = logits[:, -1].astype(jnp.float32)
    k0, k1 = jax.random.split(key)
    nxt = sample(logits, k0, **_sample_cfg(cfg))
    rows = jnp.arange(nxt.shape[0], dtype=jnp.int32)
    idx = jnp.clip(steps, 0, out_buf.shape[1] - 1)
    # finished lanes are masked: their buffer entry and step
    # count stay frozen while the fixed-batch step runs on
    out_buf = out_buf.at[rows, idx].set(
        jnp.where(finished, out_buf[rows, idx], nxt))
    steps = steps + jnp.where(finished, 0, 1)
    if cfg.eos_token_id is not None:
        finished = finished | (nxt == cfg.eos_token_id)
    finished = finished | (steps >= budget)
    # dead slots: pin kv_len at 0 so an idle lane neither wraps
    # the ring nor walks the position table out of range while
    # it waits for its next admission
    cache = cache.with_kv_len(jnp.where(finished, 0, cache.kv_len))
    return cache, DecodeLanes(nxt, finished, steps, budget, out_buf,
                              _count_routing(net, moe_counters)), k1


def spec_step_fn(net, state_vals, cache, lanes, key, cfg, spec):
    (tok, finished, steps, budget, out_buf, moe_counters, tok_buf, tok_len,
     proposed, accepted) = lanes
    draft = ngram_propose(tok_buf, tok_len, k=spec.k, n=spec.ngram)
    window = jnp.concatenate([tok[:, None], draft], axis=1)
    logits, cache = net(state_vals, window, cache=cache)
    logits = logits.astype(jnp.float32)
    k0, k1 = jax.random.split(key)
    # the shared acceptance/clamp/scatter/rollback core —
    # pin_finished_kv is the engine's idle-lane contract (a
    # parked slot must never wrap the ring)
    (tok, cache, finished, steps, out_buf, tok_buf, tok_len, proposed,
     accepted) = apply_verify_window(
        logits, draft, k0, cfg, spec, tok, cache, finished, steps, budget,
        out_buf, tok_buf, tok_len, proposed, accepted,
        pin_finished_kv=True)
    return cache, SpecLanes(tok, finished, steps, budget, out_buf,
                            _count_routing(net, moe_counters), tok_buf,
                            tok_len, proposed, accepted), k1


def block_step_fn(net, state_vals, cache, lanes, key, bd):
    (finished, steps, budget, out_buf, ustep_buf, blk, blk_step, out0,
     counters, moe_counters) = lanes
    kv0 = cache.kv_len
    logits, cache = net(state_vals, blk, cache=cache, **net.block_kw)
    logits = logits.astype(jnp.float32)
    (cache, finished, steps, out_buf, ustep_buf, blk, blk_step, out0,
     counters) = apply_block_step(
        logits, bd, cache, kv0, finished, steps, budget, out_buf,
        ustep_buf, blk, blk_step, out0, counters)
    # block diffusion draws nothing: the key goes through as it came
    return cache, BlockLanes(finished, steps, budget, out_buf, ustep_buf,
                             blk, blk_step, out0, counters,
                             _count_routing(net, moe_counters)), key


def admit_fn(mode, cache, lanes, slot, row_cache, first, *where):
    # install the batch-1 prefill row into the freed slot: the cache
    # takes the row (a page pool scatters it into the pages of
    # where = (table_row, start), SKIPPING the shared-prefix positions
    # below start: they already hold this content — prefill once,
    # reference-count many), the mode resets the slot's lanes.
    # slot/table/start are traced data — one program, every slot,
    # every layout.
    return (cache.install_row(row_cache, slot, *where),
            mode.admit(lanes, slot, first))


def free_fn(cache, lanes, slot):
    return (cache.reset_rows(slot),
            lanes._replace(finished=lanes.finished.at[slot].set(True)))


def poll_view_fn(view):
    # what a poll reads, COPIED out of the lanes (and the quantized
    # cache's counter): the step dispatched behind this program donates
    # the buffers these came from, and the host reads the copies while
    # that step runs. A plain return would hand the inputs back.
    return jax.tree_util.tree_map(jnp.copy, view)


def chunk_fn(net, state_vals, ids, row_cache):
    # one NON-final prefill chunk: decode-mode forward over the
    # persistent batch-1 side cache — attention masks at
    # kv_len + C with queries at offset kv_len (the chunk
    # kernel), the C new KV rows land in the ring, kv_len
    # advances. The logits are never read, so the LM head DCEs
    # out of the compiled program.
    _, row_cache = net(state_vals, ids, cache=row_cache)
    return row_cache


def chunk_final_fn(net, state_vals, ids, plen, key, row_cache, cfg):
    # the FINAL (pad-to-C) chunk: kv_len clamps to the true
    # prompt length, the hidden state is gathered at the last
    # REAL position, and the first token is sampled — the same
    # (tok, row_cache, key, finished) contract as prefill_fn,
    # so the EXISTING admit program installs the result
    # unchanged.
    logits, row_cache = net(state_vals, ids, cache=row_cache,
                            prompt_len=plen)
    return _first_token(logits, row_cache, key, cfg)


def install_span_fn(cache, row_cache, table_row, start):
    # commit one completed chunk's positions into the pool
    # pages the admission planner already committed — table row
    # and kv_len stay untouched, so the slot's lane stays
    # parked (null-page routed) until the final admit installs
    # the pointers atomically
    return cache.install_span(row_cache, table_row, start)


# ---------------------------------------------------------- step modes

# ``moe_counters``: the experts' rows, busiest-expert rows and rows sent
# to experts held elsewhere of every forward so far, three int32 the poll
# drains into moe.* (they stay 0 where the layer has no dropless expert
# layer)
DecodeLanes = collections.namedtuple(
    "DecodeLanes", ("tok", "finished", "steps", "budget", "out_buf",
                    "moe_counters"))
# drafter lanes: per-slot token history (prompt + emitted, the n-gram
# lookup corpus) and the on-device proposed/accepted counters the poll
# drains into gen.spec.*
SpecLanes = collections.namedtuple(
    "SpecLanes", DecodeLanes._fields + ("tok_buf", "tok_len", "proposed",
                                        "accepted"))
# block lanes (generation/block_diffusion): per token the step that
# unmasked it; the current block, its denoise step, its first output
# index; and the on-device counters the poll drains into
# gen.diffusion.* and moe.*
BlockLanes = collections.namedtuple(
    "BlockLanes", ("finished", "steps", "budget", "out_buf", "ustep_buf",
                   "blk", "blk_step", "out0", "counters", "moe_counters"))


def _zeros(*shape):
    return np.zeros(shape, np.int32)


class _StepMode:
    """What the scheduler asks of a step mode. Each gives ``key`` (its
    step program's), ``step_fn``, ``static`` (that program's static
    operands by name) and

    - ``lanes(batch, cap)``: an empty engine's lanes, on the HOST (the
      engine ``device_put``\\ s them); empty slots are masked;
    - ``first(prompt, budget, tok, fin)``: what an admission writes into
      a slot's lanes, of the host's values and the prefill's outputs.
      Host scalars go in as they are: ``jnp.asarray(x, int32)`` dispatches
      a conversion program each, and the device waits meanwhile;
    - ``admit(lanes, slot, first)``: those writes, traced;
    - ``_book(stats, *deltas)``: where drained counters go."""
    #: lanes the poll reads beside ``finished`` and ``steps``
    counters: Tuple[str, ...] = ()
    #: lanes a slot's result is read from, behind one wait
    row: Tuple[str, ...] = ("out_buf",)
    #: the shortest prompt an admission takes
    min_prompt = 1
    #: what every forward of the layer is told beside its cache
    forward_kw: Dict[str, Any] = {}
    _seen = 0     # host mirror of the counters, for poll deltas

    def check_prompt(self, ids: np.ndarray):
        """Raise for a prompt this mode cannot admit."""

    def prefill_len(self, prompt: np.ndarray) -> int:
        """How much of the prompt the prefill commits to the cache."""
        return prompt.size

    def first_avals(self, tok_a, fin_a):
        # from a call on a shortest prompt: first()'s fields stand once
        return _avals(self.first(_zeros(self.min_prompt), 1, tok_a, fin_a))

    def drain(self, counters, stats) -> dict:
        """The poll read ``counters`` (this mode's, as host arrays):
        book what they gained since the last poll; returns what the
        ``serve.poll`` span shows of it. The device counters are
        lifetime int32 and WRAP on a long-lived engine; per-poll deltas
        are tiny, so modular subtraction recovers them exactly."""
        if not counters:
            return {}
        seen = np.concatenate([np.ravel(c) for c in counters]) \
            .astype(np.int64)
        delta = (seen - self._seen) % (1 << 32)
        self._seen = seen
        return self._book(stats, *(int(d) for d in delta))

    def cut(self, req, row, n: int, partial: bool) -> np.ndarray:
        """The tokens of a lane that made ``n``, from its ``row`` lanes;
        ``partial``: the lane was evicted before it finished."""
        return row[0][:n]

    @staticmethod
    def _book_routing(rows: int, rows_max: int, elsewhere: int) -> dict:
        """The drained ``moe_counters`` into moe.*; what a ``serve.poll``
        shows of them (nothing where no expert layer ran)."""
        if not rows and not elsewhere:
            return {}
        monitor.record_moe_routing(rows, rows_max, elsewhere)
        return {"moe_rows": rows}


class Decode(_StepMode):
    """Plain decode: every live lane takes one sampled token a step."""
    key = ("step",)
    step_fn = staticmethod(step_fn)
    counters = ("moe_counters",)

    def __init__(self, cfg):
        self.cfg = cfg
        self.static = {"cfg": cfg}

    def lanes(self, batch, cap):
        return DecodeLanes(_zeros(batch), np.ones((batch,), bool),
                           _zeros(batch), _zeros(batch), _zeros(batch, cap),
                           _zeros(3))

    def _book(self, stats, *routing):
        return self._book_routing(*routing)

    def first(self, prompt, budget, tok, fin):
        return {"tok": tok, "fin": fin, "budget": np.int32(budget)}

    def admit(self, lanes, slot, first):
        # the slot's scheduler lanes after admission; the slot index is
        # a traced scalar, so one program serves every slot
        tok, budget = first["tok"][0], first["budget"]
        row = jnp.zeros((lanes.out_buf.shape[1],), jnp.int32).at[0].set(tok)
        return lanes._replace(
            tok=lanes.tok.at[slot].set(tok),
            finished=lanes.finished.at[slot].set(
                first["fin"][0] | (budget <= 1)),
            steps=lanes.steps.at[slot].set(1),
            budget=lanes.budget.at[slot].set(budget),
            out_buf=lanes.out_buf.at[slot].set(row))


class Speculative(Decode):
    """N-gram speculation: a fused prompt-lookup draft and one verify
    forward; every live lane advances 1..k+1 tokens a step."""
    key = ("spec_step",)
    step_fn = staticmethod(spec_step_fn)
    counters = Decode.counters + ("proposed", "accepted")

    def __init__(self, cfg, spec, max_len: int):
        super().__init__(cfg)
        self.static = {"cfg": cfg, "spec": spec}
        self.max_len = max_len

    def lanes(self, batch, cap):
        return SpecLanes(*super().lanes(batch, cap),
                         _zeros(batch, self.max_len), _zeros(batch),
                         _zeros(), _zeros())

    def first(self, prompt, budget, tok, fin):
        # the drafter's corpus row: the full-width padded prompt (the
        # admit program appends the prefill token in-trace)
        ids_row = np.full((self.max_len,), self.cfg.pad_value, np.int32)
        ids_row[:prompt.size] = prompt
        return dict(super().first(prompt, budget, tok, fin),
                    ids_row=ids_row, plen=np.int32(prompt.size))

    def admit(self, lanes, slot, first):
        lanes = super().admit(lanes, slot, first)
        # the drafter's token history: the padded prompt row with
        # the prefill token appended — the n-gram drafter reads
        # prompt AND emitted tokens from one buffer
        plen = first["plen"]
        row = first["ids_row"].at[plen].set(first["tok"][0])
        return lanes._replace(tok_buf=lanes.tok_buf.at[slot].set(row),
                              tok_len=lanes.tok_len.at[slot].set(plen + 1))

    def _book(self, stats, rows, rows_max, elsewhere, proposed, accepted):
        if proposed or accepted:
            stats["spec_proposed"] += proposed
            stats["spec_accepted"] += accepted
            monitor.record_speculative(proposed, accepted)
        return self._book_routing(rows, rows_max, elsewhere)


class BlockDiffusion(_StepMode):
    """Block diffusion: the step forwards each lane's block and unmasks
    its most confident positions or, once none is masked, commits it
    and opens the next (``generation/block_diffusion.py``). Greedy, to
    the budget: nothing is sampled."""
    key = ("block_step",)
    step_fn = staticmethod(block_step_fn)
    # forwards / unmasked / commits, then the experts' three routing
    # counts: six int32 scalars in the poll's window
    counters = ("counters", "moe_counters")
    # both rows behind ONE wait: a second blocking read is a second
    # round trip during which the device has nothing queued
    row = ("out_buf", "ustep_buf")

    def __init__(self, bd):
        self.bd = bd
        self.static = {"bd": bd}
        self.min_prompt = bd.block_length
        self.forward_kw = {"block_length": bd.block_length}

    def lanes(self, batch, cap):
        return BlockLanes(
            np.ones((batch,), bool), _zeros(batch), _zeros(batch),
            _zeros(batch, cap), np.full((batch, cap), -1, np.int8),
            np.full((batch, self.bd.block_length), self.bd.mask_token_id,
                    np.int32),
            _zeros(batch), _zeros(batch), _zeros(3), _zeros(3))

    def check_prompt(self, ids):
        if ids.size < self.bd.block_length:
            raise ValueError(
                f"prompt of {ids.size} tokens is shorter than one block "
                f"({self.bd.block_length}): block diffusion commits the "
                "prompt's whole blocks before it generates")

    def prefill_len(self, prompt):
        # only the prompt's whole blocks are committed; what is left
        # over opens the first generated block
        return first_block(prompt, self.bd)[0]

    def first(self, prompt, budget, tok, fin):
        _, blk, out0 = first_block(prompt, self.bd)
        return {"budget": np.int32(budget), "blk": blk,
                "out0": np.int32(out0)}

    def admit(self, lanes, slot, first):
        # the prefill row holds the prompt's whole blocks; the lane
        # opens on the first generated block (the prompt's left-over
        # tokens, then masks)
        budget = first["budget"]
        return lanes._replace(
            finished=lanes.finished.at[slot].set(budget < 1),
            steps=lanes.steps.at[slot].set(0),
            budget=lanes.budget.at[slot].set(budget),
            out_buf=lanes.out_buf.at[slot].set(0),
            ustep_buf=lanes.ustep_buf.at[slot].set(-1),
            blk=lanes.blk.at[slot].set(first["blk"]),
            blk_step=lanes.blk_step.at[slot].set(0),
            out0=lanes.out0.at[slot].set(first["out0"]))

    def _book(self, stats, forwards, unmasked, commits, *routing):
        stats["diffusion_forwards"] += forwards
        stats["diffusion_commits"] += commits
        monitor.record_block_diffusion(forwards, unmasked, commits)
        return dict(self._book_routing(*routing),
                    forwards=forwards, commits=commits)

    def cut(self, req, row, n, partial):
        toks, usteps = row
        if partial:
            # tokens are unmasked out of order inside a block: the
            # partial result is the prefix before the first position
            # still masked
            n = int(np.argmax(np.append(usteps, -1) < 0))
        req.unmask_steps = usteps[:n]
        return toks[:n]


# ------------------------------------------------------------ the table

#: what one poll reads: the mode's ``counters`` and ``row`` lanes as tuples,
#: the cache's ``kv_len`` (the occupancy gauge) and a quantized cache's
#: saturation counter (None where there is none)
PollView = collections.namedtuple(
    "PollView", ("finished", "steps", "counters", "kv_len", "clips", "rows"))


def poll_view(mode, cache, lanes) -> PollView:
    """The live buffers a poll reads when nothing runs behind its read;
    the ``poll_view`` program's copies of them when a step does: nothing
    a poll reads may wait for that step."""
    return PollView(lanes.finished, lanes.steps,
                    tuple(getattr(lanes, n) for n in mode.counters),
                    cache.kv_len, getattr(cache, "clips", None),
                    tuple(getattr(lanes, n) for n in mode.row))


def program_table(net: Network, mode, cfg, *, state, cache, lanes, key,
                  buckets, max_len: int, chunk=None,
                  pages_per_row=None) -> Dict[tuple, Program]:
    """Every program the engine can dispatch, by its key, in warm-up
    order: a prefill per bucket, the mode's step, admit and free, the
    poll's view (the one program that donates nothing and is not part of
    the cache's round trip: a copy of a few small lanes), and
    with ``chunk`` (tokens a prefill chunk) the chunk pair and, over a
    page pool (``pages_per_row``), the span install. ``state`` is held as
    given (never donated; its placement is the lowering's); the donated
    ``cache``, ``lanes`` and ``key`` as avals."""
    cache, lanes, key = _avals((cache, lanes, key))
    one = _sds((1,), jnp.int32)

    def ids(n):
        return _sds((1, n), jnp.int32)

    @functools.lru_cache(maxsize=None)
    def row():
        """(tok, row_cache, finished) avals of a batch-1 prefill — the
        admit program's source operands (bucket-independent: every
        bucket prefills into a cache of the shared max_len)."""
        tok_a, row_cache_a, _, fin_a = jax.eval_shape(
            lambda s, i, p, k: prefill_fn(net, s, i, p, k, cfg, max_len),
            state, ids(buckets[0]), one, key)
        return tok_a, row_cache_a, fin_a

    # where a page pool installs a row: its page table and the first
    # position the install writes (a dense cache needs neither)
    where = {} if pages_per_row is None else {
        "table_row": _sds((pages_per_row,), jnp.int32), "start": _SCALAR}
    progs = [Program(("prefill", b), _bind(prefill_fn, net),
                     dict(state=state, ids=ids(b), plen=one, key=key,
                          cfg=cfg, cache_len=max_len),
                     static=("cfg", "cache_len"), name=f"prefill.{b}")
             for b in buckets]
    progs += [
        # every lane round-trips in place across scheduler steps
        Program(mode.key, _bind(mode.step_fn, net),
                dict(state=state, cache=cache, lanes=lanes, key=key,
                     **mode.static),
                static=tuple(mode.static),
                donates=("cache", "lanes", "key"),
                name="decode", report="decode"),
        # the admit donates the prefill row too: it is read once
        Program(("admit",), _bind(admit_fn, mode),
                dict(cache=cache, lanes=lanes, slot=_SCALAR,
                     row_cache=lambda: row()[1],
                     first=lambda: mode.first_avals(row()[0], row()[2]),
                     **where),
                donates=("cache", "lanes", "row_cache"),
                name="admit", report="admit"),
        Program(("free",), free_fn,
                dict(cache=cache, lanes=lanes, slot=_SCALAR),
                donates=("cache", "lanes"), name="free", report="free"),
        Program(("poll_view",), poll_view_fn,
                dict(view=poll_view(mode, cache, lanes)),
                name="poll_view", report="poll_view"),
    ]
    if chunk is not None:
        # chunk programs: the side cache is the ONLY donated operand —
        # it round-trips in place every chunk; the span install donates
        # the pool but NOT the source side cache, which the next chunk
        # still reads
        side = row()[1]
        progs += [
            Program(("chunk", chunk), _bind(chunk_fn, net),
                    dict(state=state, ids=ids(chunk), row_cache=side),
                    donates=("row_cache",), name=f"prefill_chunk.{chunk}"),
            Program(("chunk_final", chunk), _bind(chunk_final_fn, net),
                    dict(state=state, ids=ids(chunk), plen=one, key=key,
                         row_cache=side, cfg=cfg),
                    static=("cfg",), donates=("row_cache",),
                    name=f"prefill_chunk_final.{chunk}"),
        ]
        if pages_per_row is not None:
            progs.append(Program(
                ("install_span",), install_span_fn,
                dict(cache=cache, row_cache=side, **where),
                donates=("cache",), name="install_span"))
    return {p.key: p for p in progs}

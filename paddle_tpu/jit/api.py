"""Graph capture: the @to_static analog.

Reference analog: paddle.jit @to_static rewrites Python AST into a static
ProgramDesc (python/paddle/fluid/dygraph/dygraph_to_static/
program_translator.py) executed by run_program. On TPU there is no AST
surgery: Layer code is already pure jax underneath (the tape skips
recording for Tracers), so capture == `jax.jit` over a functionalized
view of (parameters, buffers, inputs). Compile caching is jax's; the
whole train step compiles to ONE XLA program — the design goal the
reference's InterpreterCore + fused kernels approximate.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from . import compile_cache
from ..core import flight_recorder as _flight_recorder
from ..core import monitor
from ..core.tensor import Parameter, Tensor, no_grad
from ..optimizer.optimizer import opt_key as _opt_key
from ..nn.layer import Layer


def _unwrap(x):
    return x._data if isinstance(x, Tensor) else x


class _RetraceTracker:
    """Classifies jax.jit cache misses into the metrics registry:
    first | new_shape | new_dtype | new_structure | donation_miss (the
    signature was seen but the jit cache still grew — donation or
    weak-type mismatch). Zero work unless the monitor is enabled."""

    # cap remembered signatures: under pathological dynamic shapes the
    # classifier degrades gracefully (oldest evicted) instead of scanning
    # and retaining an unbounded history
    MAX_SEEN = 256

    def __init__(self):
        import collections
        self._seen = collections.deque(maxlen=self.MAX_SEEN)
        self._seen_set = set()

    @staticmethod
    def _signature(trees):
        """(treedef, ((shape, dtype), ...)) — treedef included because
        it is part of jax's jit cache key (same leaves under a different
        container nesting still retrace)."""
        leaves, treedef = jax.tree_util.tree_flatten(trees)
        sig = []
        for v in leaves:
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                sig.append((tuple(v.shape), str(v.dtype)))
            else:
                sig.append((type(v).__name__, ""))
        return (str(treedef), tuple(sig))

    def _classify(self, sig) -> str:
        if not self._seen:
            return "first"
        tdef, leaves = sig
        if any(s_leaves == leaves and s_tdef != tdef
               for s_tdef, s_leaves in self._seen):
            return "new_structure"
        same_len = [s_leaves for _, s_leaves in self._seen
                    if len(s_leaves) == len(leaves)]
        if not same_len:
            return "new_structure"
        shapes = tuple(s for s, _ in leaves)
        dtypes = tuple(d for _, d in leaves)
        for s in same_len:
            if tuple(d for _, d in s) == dtypes:
                return "new_shape"
        for s in same_len:
            if tuple(sh for sh, _ in s) == shapes:
                return "new_dtype"
        return "new_structure"

    @staticmethod
    def _cache_of(jitted):
        try:
            return jitted._cache_size()
        except Exception:
            return None

    def pre(self, jitted):
        """Call BEFORE the jitted call: cache size going in, or None
        when neither the monitor nor the flight recorder is on
        (observe() will no-op)."""
        if not (monitor.enabled or _flight_recorder.enabled):
            return None
        return self._cache_of(jitted)

    def observe(self, jitted, trees, pre_cache):
        """Call AFTER the jitted call with pre()'s value. A retrace is
        counted only when the compiled cache actually grew during this
        call, so enabling the monitor against a warmed function never
        reports phantom compiles; without cache introspection the
        signature novelty is the (over-approximate) fallback. Runs for
        the flight recorder too — a post-mortem must show what
        compiled even when the metrics registry was never enabled
        (monitor.record_retrace feeds both streams). Returns whether
        the jit cache grew during this call (a program was built)."""
        if not (monitor.enabled or _flight_recorder.enabled):
            return False
        cache = self._cache_of(jitted)
        known = cache is not None and pre_cache is not None
        compiled = known and cache > pre_cache
        if not monitor.enabled and known and not compiled:
            # flight-recorder-only mode: nothing compiled this call, so
            # skip the per-leaf signature walk — the black box only
            # needs the (rare) compile events, not a hot-path tax
            return False
        sig = self._signature(trees)
        if sig in self._seen_set:
            if compiled:
                monitor.record_retrace("donation_miss")
            return compiled
        if compiled or not known:
            monitor.record_retrace(self._classify(sig))
        if len(self._seen) == self.MAX_SEEN:
            self._seen_set.discard(self._seen[0])  # deque evicts it
        self._seen_set.add(sig)
        self._seen.append(sig)
        return compiled


def _wrap(x):
    return Tensor(x) if isinstance(x, jax.Array) else x


def _note_built(sp, label: str, hits_before: int):
    """The jit cache grew during the call ``sp`` (a ``train.step``
    span) covers: it traced, lowered and compiled the step (or fetched
    it from jax's persistent cache) before it dispatched it."""
    sp.set(compiled=1)
    _flight_recorder.record_span(
        "jit.program", sp.start_ns, _flight_recorder.now_ns(),
        parent=sp.id, label=label,
        source=compile_cache.compile_source(hits_before))


def functional_call(layer: Layer, params_and_buffers: Dict[str, Any],
                    *args, **kwargs):
    """Run `layer` with parameter/buffer values taken from the dict
    (name -> array/Tensor), without mutating the layer. The bridge between
    the stateful Layer API and jax transforms (≈ torch.func.functional_call;
    no reference analog — Paddle's static bridge is dy2static)."""
    state = layer.state_dict()
    saved = {name: t._data for name, t in state.items()}
    try:
        for name, value in params_and_buffers.items():
            if name in state:
                state[name]._data = _unwrap(value)
        with no_grad():
            out = layer(*args, **kwargs)
        return out
    finally:
        for name, t in state.items():
            t._data = saved[name]


def to_static(function=None, input_spec=None, full_graph=True, backend=None,
              donate_params: bool = False, static_argnums=()):
    """Decorator: compile a function or Layer.forward with jax.jit.
    Tensor args are passed as traced arrays; outputs come back as Tensors.
    For a Layer, parameters/buffers are captured as traced constants
    re-read on every call (so `opt.step()` updates are seen) but donate
    nothing; use TrainStep for the fused, donated training path."""

    def deco(fn):
        is_layer = isinstance(fn, Layer)
        target = fn.forward if is_layer else fn
        if getattr(target, "__jit_not_to_static__", False):
            return fn  # @not_to_static: stay eager
        # dy2static pass: tensor-dependent if/while become
        # lax.cond/while_loop before jax.jit traces the function
        if not is_layer:
            from .dy2static import convert_to_static
            target = convert_to_static(target)

        @functools.partial(jax.jit, static_argnums=static_argnums)
        def jitted(state_vals, arg_vals, kw_vals):
            if is_layer:
                names = jitted._state_names
                out = functional_call(fn, dict(zip(names, state_vals)),
                                      *arg_vals, **kw_vals)
            else:
                with no_grad():
                    out = target(*arg_vals, **kw_vals)
            return jax.tree_util.tree_map(_unwrap, out,
                                          is_leaf=lambda x: isinstance(x, Tensor))

        jitted._state_names = None
        tracker = _RetraceTracker()

        @functools.wraps(target)
        def wrapper(*args, **kwargs):
            if not ProgramTranslator.enable_to_static:
                # global kill-switch: run the ORIGINAL eagerly so
                # breakpoints/prints work (reference
                # ProgramTranslator.enable(False) semantics)
                return fn(*args, **kwargs) if is_layer else \
                    (fn(*args, **kwargs))
            if is_layer:
                state = fn.state_dict()
                jitted._state_names = list(state.keys())
                state_vals = tuple(t._data for t in state.values())
            else:
                state_vals = ()
            arg_vals = jax.tree_util.tree_map(
                _unwrap, args, is_leaf=lambda x: isinstance(x, Tensor))
            kw_vals = jax.tree_util.tree_map(
                _unwrap, kwargs, is_leaf=lambda x: isinstance(x, Tensor))
            pre_cache = tracker.pre(jitted)
            out = jitted(state_vals, arg_vals, kw_vals)
            tracker.observe(jitted, (state_vals, arg_vals, kw_vals),
                            pre_cache)
            return jax.tree_util.tree_map(_wrap, out)

        wrapper.__wrapped_layer__ = fn if is_layer else None
        wrapper._jitted = jitted
        return wrapper

    if function is not None:
        return deco(function)
    return deco


jit = to_static  # alias


def grad(*fargs, **fkwargs):
    """Dual-personality `paddle.grad`:

    - grad(fn, argnums=0, has_aux=False) -> functional transform
      (jax.grad with Tensor marshalling), the jit-compatible autodiff.
    - grad(outputs, inputs, grad_outputs=None, retain_graph=None,
      create_graph=False, only_inputs=True, allow_unused=False,
      no_grad_vars=None) -> reference dygraph API
      (python/paddle/fluid/dygraph/base.py grad()): tape-based grads of
      output Tensors w.r.t. input Tensors, incl. create_graph=True for
      double grad. Delegates to autograd.backward_engine.tensor_grad.
    """
    if fargs and callable(fargs[0]) and not isinstance(fargs[0], Tensor):
        return _functional_grad(*fargs, **fkwargs)
    from ..autograd.backward_engine import tensor_grad
    return tensor_grad(*fargs, **fkwargs)


def _functional_grad(fn: Callable, argnums=0, has_aux: bool = False):
    def wrapped(*args, **kwargs):
        def pure(*raw_args):
            targs = jax.tree_util.tree_map(_wrap, raw_args)
            out = fn(*targs, **kwargs)
            return jax.tree_util.tree_map(
                _unwrap, out, is_leaf=lambda x: isinstance(x, Tensor))

        raw = jax.tree_util.tree_map(
            _unwrap, args, is_leaf=lambda x: isinstance(x, Tensor))
        g = jax.grad(pure, argnums=argnums, has_aux=has_aux)(*raw)
        return jax.tree_util.tree_map(_wrap, g)

    return wrapped


def value_and_grad(fn: Callable, argnums=0, has_aux: bool = False):
    def wrapped(*args, **kwargs):
        def pure(*raw_args):
            targs = jax.tree_util.tree_map(_wrap, raw_args)
            out = fn(*targs, **kwargs)
            return jax.tree_util.tree_map(
                _unwrap, out, is_leaf=lambda x: isinstance(x, Tensor))

        raw = jax.tree_util.tree_map(
            _unwrap, args, is_leaf=lambda x: isinstance(x, Tensor))
        v, g = jax.value_and_grad(pure, argnums=argnums,
                                  has_aux=has_aux)(*raw)
        return (jax.tree_util.tree_map(_wrap, v),
                jax.tree_util.tree_map(_wrap, g))

    return wrapped


class TrainStep:
    """Fused, donated training step: (params, opt_state, batch) -> (loss,
    params', opt_state') as ONE compiled XLA program.

    This is the TPU answer to the reference's per-op dygraph loop + fused
    optimizer kernels + Reducer overlap: forward, backward, (clip), update
    all fuse under XLA, with parameter buffers donated so updates are
    in-place in HBM.

    Usage:
        step = TrainStep(model, opt, loss_fn)
        for batch in loader:
            loss = step(batch_inputs, labels)   # updates model in place
    Sharding: pass in_shardings/mesh via `sharding` (see distributed.fleet).
    """

    def __init__(self, model: Layer, optimizer, loss_fn: Callable,
                 donate: bool = True, sharding=None,
                 offload_opt_state: bool = False,
                 skip_nonfinite: bool = False, recompute=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._sharding = sharding
        # recompute: a fleet.utils.RecomputeConfig (or policy name) —
        # the whole forward becomes a jax.checkpoint region under the
        # config's policy, trading backward FLOPs for activation HBM
        # without touching the model definition
        if recompute is not None:
            from ..distributed.fleet.utils.recompute import _as_config
            recompute = _as_config(recompute)
        self._recompute = recompute
        # skip_nonfinite: the in-jit half of the resilience layer's
        # anomaly guard — a non-finite loss keeps params/opt state
        # unchanged (the jnp.where select fuses away; same pattern as
        # GradScaler's found_inf skip), the poisoned loss still returns
        # for the host-side AnomalyGuard to count.
        self._skip_nonfinite = skip_nonfinite
        # offload_opt_state: park optimizer moments in host memory
        # (pinned_host) between steps — HBM relief for big-batch /
        # long-seq configs at the cost of PCIe streaming per step (the
        # reference's sharding offload, group_sharded_storage.py).
        # Falls back silently where the backend lacks memory kinds.
        self._offload = offload_opt_state
        self._host_shardings = None

        self._param_names = [n for n, _ in model.named_parameters()]
        # the Parameter objects themselves: cached so the hot loop does
        # not re-walk the module tree (names + containers) every step
        self._params_cache = [p for _, p in model.named_parameters()]
        self._opt_state_tree = None

        def step_fn(param_vals, opt_state, lr, step_no, *batch):
            params = dict(zip(self._param_names, param_vals))

            def loss_of(pvals):
                pdict = dict(zip(self._param_names, pvals))
                out = functional_call(self.model, pdict, *batch[:-1])
                loss = self.loss_fn(
                    out, jax.tree_util.tree_map(_wrap, batch[-1]))
                return _unwrap(loss)

            if self._recompute is not None and self._recompute.enabled:
                loss_of = self._recompute.wrap(loss_of)
            loss, grads = jax.value_and_grad(loss_of)(list(param_vals))
            new_params, new_state = self.optimizer.apply_gradients(
                list(param_vals), grads, opt_state, lr=lr, step=step_no)
            if self._skip_nonfinite:
                import jax.numpy as jnp
                ok = jnp.isfinite(loss)
                new_params = [jnp.where(ok, n, o)
                              for n, o in zip(new_params, param_vals)]
                new_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o),
                    new_state, opt_state)
            return loss, new_params, new_state

        donate_argnums = (0, 1) if donate else ()
        self._step_fn = step_fn
        self._donate_argnums = donate_argnums
        self._jitted = jax.jit(step_fn, donate_argnums=donate_argnums)
        self._tracker = _RetraceTracker()
        self._warm_store = None   # enable_warm_start() opt-in
        self._warm_exe = None
        # the warm/AOT path bakes donation only where the backend
        # implements it: a serialized executable REPLAYS its
        # input_output_aliases on load, and deserialized-on-CPU
        # aliasing double-frees the donated buffers (heap corruption)
        # where the live jit path merely drops the request with a
        # warning. audit() keeps gating the donation INTENT.
        self._aot_donate = donate_argnums \
            if jax.default_backend() == "tpu" else ()
        self._aot_jitted = self._jitted \
            if self._aot_donate == donate_argnums \
            else jax.jit(step_fn, donate_argnums=self._aot_donate)

    def enable_warm_start(self, store=None):
        """Opt-in executable persistence for the fused step — the
        ``Model.fit(resume=True)`` warm path. The first call lowers the
        step and loads a serialized executable from ``store`` (default:
        the ``jit.compile_cache`` process store), so a relaunched
        trainer reaches its first step in load time, not compile time;
        a cold store compiles once and persists for the next relaunch.
        Dispatch falls back to the regular jit path the moment the
        operand signature drifts from the warmed executable.

        No-op under ``offload_opt_state``: the offload path re-jits a
        ``device_put``-wrapped program in ``_setup_offload``, and
        persisting the resident-state variant would silently disable
        the offload (and its HBM relief) on relaunch."""
        if self._offload:
            return self
        from . import compile_cache
        self._warm_store = store if store is not None \
            else compile_cache.default_store()
        return self

    def _warm_signature(self, args):
        """Structural identity of the fused step WITHOUT tracing it
        (the store's traceless manifest key): model code + config,
        loss/optimizer code and their baked scalar constants, the
        recompute/skip flags, and the full operand aval tree. None —
        forcing the always-correct traced path — when any piece has no
        deterministic description (REPL lambdas, address-bearing
        reprs, opaque closure cells)."""
        from . import compile_cache
        sig = compile_cache.network_signature(self.model)
        loss_sig = compile_cache.callable_signature(self.loss_fn)
        opt_src = compile_cache.source_hash(type(self.optimizer))
        flags = repr((self._skip_nonfinite, self._offload,
                      self._recompute))
        if sig is None or loss_sig is None or opt_src is None \
                or "0x" in flags:
            return None
        sig.update(
            program=("TrainStep",), loss=loss_sig,
            opt=(type(self.optimizer).__qualname__, opt_src,
                 compile_cache.scalar_signature(self.optimizer)),
            flags=flags,
            operands=compile_cache.aval_signature(args))
        return sig

    def _setup_offload(self):
        """Re-jit with the opt state parked in pinned host memory: the
        step transfers moments host->HBM, updates, and writes them back
        host-side, so they are never HBM-resident between steps."""
        leaves = jax.tree_util.tree_leaves(self._opt_state_tree)
        dev = next(iter(leaves[0].devices())) if leaves \
            else jax.devices()[0]
        if dev.platform != "tpu":
            self._offload = False  # only TPU has a distinct host space
            return
        try:
            host = jax.sharding.SingleDeviceSharding(
                dev, memory_kind="pinned_host")
            devmem = jax.sharding.SingleDeviceSharding(
                dev, memory_kind="device")
            state_sh = jax.tree_util.tree_map(
                lambda _: host, self._opt_state_tree)
            inner = self._step_fn

            def offload_step(param_vals, opt_state, lr, step_no, *batch):
                opt_dev = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, devmem), opt_state)
                loss, new_params, new_state = inner(
                    param_vals, opt_dev, lr, step_no, *batch)
                new_host = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, host), new_state)
                return loss, new_params, new_host

            self._jitted = jax.jit(
                offload_step, donate_argnums=self._donate_argnums)
            self._opt_state_tree = jax.device_put(
                self._opt_state_tree, state_sh)
            self._host_shardings = state_sh
        except Exception:
            # backend without memory-kind support: resident-state path
            self._jitted = jax.jit(
                self._step_fn, donate_argnums=self._donate_argnums)
            self._offload = False

    def __call__(self, *batch):
        # the host side of one step (argument flattening, tracker,
        # dispatch) is a train.step span; the device runs on after it
        with _flight_recorder.span("train.step") as sp:
            return self._call(sp, batch)

    def _call(self, sp, batch):
        params = self._params_cache
        if self._opt_state_tree is None:
            # seed from the optimizer's own state when present (e.g. a
            # restored checkpoint via opt.set_state_dict) so resume works
            self._opt_state_tree = [
                self.optimizer._state.get(_opt_key(p))
                or self.optimizer.init_state_for(p) for p in params]
            if self._offload:
                self._setup_offload()
        lr = self.optimizer.get_lr()
        self.optimizer._step_count += 1
        raw_batch = tuple(
            jax.tree_util.tree_map(
                _unwrap, b, is_leaf=lambda t: isinstance(t, Tensor))
            for b in batch)
        args = ([p._data for p in params], self._opt_state_tree,
                np.float32(lr), np.int32(self.optimizer._step_count),
                *raw_batch)
        if self._warm_store is not None and self._warm_exe is None:
            self._warm_exe = compile_cache.build_or_load(
                self._warm_signature(args),
                lambda: self._aot_jitted.lower(*args),
                store=self._warm_store,
                extra=dict(kind="TrainStep",
                           donation=self._aot_donate),
                label="train_step")
            self._warm_store = None  # warmed once; drift falls back
            sp.set(compiled=1)
        if self._warm_exe is not None:
            try:
                loss, new_vals, self._opt_state_tree = \
                    self._warm_exe(*args)
            except (TypeError, ValueError) as e:
                # operand signature drifted from the warmed executable
                # (input validation fails BEFORE execution — no donated
                # buffer was consumed): permanent fallback to jit
                monitor.record_swallowed("jit.compile_cache.warm_step",
                                         e)
                self._warm_exe = None
        if self._warm_exe is None:
            pre_cache = self._tracker.pre(self._jitted)
            hits = compile_cache.persistent_cache_hits() \
                if pre_cache is not None else 0
            loss, new_vals, self._opt_state_tree = self._jitted(*args)
            # donated args keep their aval metadata
            if self._tracker.observe(
                    self._jitted, (args[0], raw_batch), pre_cache):
                _note_built(sp, "train_step", hits)
        for p, v in zip(params, new_vals):
            p._data = v
        # mirror the functional state back so optimizer.state_dict()
        # checkpoints the live accumulators
        for p, st in zip(params, self._opt_state_tree):
            self.optimizer._state[_opt_key(p)] = st
        from ..optimizer.lr import LRScheduler
        if isinstance(self.optimizer._lr, LRScheduler) and \
                self.optimizer._lr._step_each_iter:
            self.optimizer._lr.step()
        return _wrap(loss)

    def audit(self, *batch, **audit_kw):
        """Static audit of the fused training step (analysis.audit):
        traces step_fn on abstract operands — nothing executes, no
        buffer is allocated — and runs the detector passes (donation
        misses, host callbacks, dtype leaks, baked consts, collective
        accounting). The tier-1 gate asserts zero ERROR findings and
        full donation coverage of params + optimizer state."""
        from ..analysis import abstractify, audit as _audit
        params = self._params_cache
        p_avals = [jax.ShapeDtypeStruct(tuple(p._data.shape),
                                        p._data.dtype) for p in params]
        if self._opt_state_tree is not None:
            opt_avals = abstractify(self._opt_state_tree)
        else:
            opt_avals = [jax.eval_shape(self.optimizer.init_state_for,
                                        p._data) for p in params]
        raw_batch = tuple(
            jax.tree_util.tree_map(
                _unwrap, b, is_leaf=lambda t: isinstance(t, Tensor))
            for b in batch)
        audit_kw.setdefault("name", "TrainStep.step_fn")
        return _audit(
            self._step_fn, p_avals, opt_avals,
            jax.ShapeDtypeStruct((), np.float32),
            jax.ShapeDtypeStruct((), np.int32), *abstractify(raw_batch),
            donate=self._donate_argnums, **audit_kw)

    def lower(self, *batch):
        """jax Lowered for the step on these example inputs (the same
        entry ``fleet.DistributedTrainStep.lower`` offers): compile it
        for ``cost_analysis()`` or to read the program's text."""
        params = self._params_cache
        if self._opt_state_tree is None:
            self._opt_state_tree = [
                self.optimizer._state.get(_opt_key(p))
                or self.optimizer.init_state_for(p) for p in params]
            if self._offload:
                # keep offload active even when cost_analysis seeds the
                # state before the first real step
                self._setup_offload()
        raw_batch = tuple(
            jax.tree_util.tree_map(
                _unwrap, b, is_leaf=lambda t: isinstance(t, Tensor))
            for b in batch)
        return self._jitted.lower(
            [p._data for p in params], self._opt_state_tree,
            np.float32(self.optimizer.get_lr()),
            np.int32(self.optimizer._step_count + 1), *raw_batch)

    def cost_analysis(self, *batch):
        """XLA's cost model for the compiled step on these inputs
        (['flops'], bytes accessed, ...) — bench.py derives MFU from it
        instead of hand-maintained per-model formulas (the reference's
        op cost-model table, cost_model/static_op_benchmark.json, is a
        measured equivalent)."""
        return self.lower(*batch).compile().cost_analysis()


def not_to_static(fn=None):
    """Mark a function to stay un-converted under @to_static (reference
    jit/api.py not_to_static)."""
    def deco(f):
        f.__jit_not_to_static__ = True
        return f

    return deco(fn) if fn is not None else deco


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """dy2static transformed-code logging (reference
    dygraph_to_static/logging_utils.set_code_level)."""
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    """dy2static verbosity (reference logging_utils.set_verbosity)."""
    set_code_level(level, also_to_stdout)


class ProgramTranslator:
    """Singleton toggling dy2static conversion globally (reference
    dygraph_to_static/program_translator.py ProgramTranslator). Here
    conversion happens in to_static itself; the toggle makes
    @to_static fall back to eager when disabled."""

    _instance = None
    enable_to_static = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool):
        type(self).enable_to_static = bool(enable_to_static)


class TracedLayer:
    """Trace a dygraph Layer into a compiled callable + saved artifact
    (reference fluid/dygraph/jit.py TracedLayer over the legacy
    tracer; here: to_static capture + jit save)."""

    def __init__(self, layer: Layer, inputs):
        self._layer = layer
        self._compiled = to_static(layer)
        self._example = inputs

    @staticmethod
    def trace(layer: Layer, inputs):
        traced = TracedLayer(layer, inputs)
        return traced(*inputs), traced

    def __call__(self, *inputs):
        return self._compiled(*inputs)

    def save_inference_model(self, path, feed=None, fetch=None):
        from .save_load import save as jit_save
        jit_save(self._layer, path, input_spec=list(self._example))


def TranslatedLayer(path):
    """Load a saved program as a callable layer-like object (reference
    jit/translated_layer.py TranslatedLayer; here the jit.load result
    plays that role directly)."""
    from .save_load import load as jit_load
    return jit_load(path)

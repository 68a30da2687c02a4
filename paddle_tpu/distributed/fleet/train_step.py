"""DistributedTrainStep: the hybrid-parallel fused training step.

This is where the reference's whole distributed runtime collapses into one
XLA program: Reducer grad bucketing+allreduce (imperative/reducer.cc:451),
sharding stage1/2/3 reduce-scatter/all-gather (group_sharded_stage2/3.py),
TP collectives (mp_ops.py), and comm/compute overlap (ProcessGroupNCCL
comm streams) are ALL emitted by XLA's SPMD partitioner + latency-hiding
scheduler from the shardings declared here:

  params:    per-layer spec (mp) composed with ZeRO stage>=3 (sharding)
  grads:     constrained to ZeRO stage>=2 specs (reduce-scatter fusion)
  opt state: ZeRO stage>=1 specs
  batch:     sharded over (dp, sharding) on dim 0
  loss mean: global psum inserted automatically by the partitioner

Gradient accumulation (the reference's gradient_merge /
GradientMergeOptimizer) is a lax.scan over microbatches inside the same
program.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...optimizer.optimizer import opt_key as _opt_key
from ...core import flight_recorder
from ...core.tensor import Tensor
from ...jit import compile_cache
from ...jit.api import (_RetraceTracker, _note_built, functional_call,
                        _unwrap, _wrap)
from ...nn.layer import Layer
from .. import topology
from ..parallel.sharding import ShardingStrategy

DATA_AXES = ("dp", "sharding")  # batch dim shards over both (ZeRO axes
# are data-parallel axes too — fleet's sharding group is a dp subgroup)


def _param_base_spec(p) -> P:
    return getattr(p, "spec", P())


def shard_model(model: Layer, mesh: Optional[Mesh] = None,
                strategy: Optional[ShardingStrategy] = None):
    """Place every parameter according to its spec (+ ZeRO stage 3).
    ≈ the initial broadcast/partition pass of DataParallel/stage3."""
    mesh = mesh or topology.get_mesh()
    if mesh is None:
        return model
    strategy = strategy or ShardingStrategy(stage=0)
    for _, p in model.named_parameters():
        spec = strategy.param_spec(tuple(p.data.shape), mesh,
                                   _param_base_spec(p))
        p._data = jax.device_put(p._data, NamedSharding(mesh, spec))
    for _, b in model.named_buffers():
        b._data = jax.device_put(
            b._data, NamedSharding(mesh, getattr(b, "spec", P())))
    return model


class DistributedTrainStep:
    """Sharded, donated, fused train step over the active hybrid mesh.

    loss_fn(outputs, labels) -> scalar mean loss over the GLOBAL batch.
    accumulate_steps>1 runs gradient accumulation as an in-program scan
    over leading-dim microbatches (inputs get an extra leading dim).
    """

    def __init__(self, model: Layer, optimizer, loss_fn: Callable,
                 mesh: Optional[Mesh] = None, donate: bool = True,
                 accumulate_steps: int = 1, abstract: bool = False,
                 recompute=None):
        """abstract=True skips placing parameters on the mesh (and
        lower_abstract() skips optimizer/batch buffers too): the step
        can then only be LOWERED, not executed — compile-planning a
        mesh whose replicated state would not fit host memory (e.g. a
        256-chip plan on a virtual CPU mesh)."""
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or topology.get_mesh()
        if self.mesh is None:
            raise RuntimeError("No mesh: call fleet.init(strategy) first")
        self.strategy: ShardingStrategy = getattr(
            optimizer, "_sharding_strategy", ShardingStrategy(stage=0))
        self.accumulate_steps = accumulate_steps
        self.abstract = abstract
        # recompute: fleet.utils.RecomputeConfig (or policy name) —
        # wraps the whole per-microbatch forward in jax.checkpoint so
        # long-context configs trade backward FLOPs for activation HBM
        # (and with it, batch size) without editing the model
        if recompute is not None:
            from .utils.recompute import _as_config
            recompute = _as_config(recompute)
        self._recompute = recompute

        if not abstract:
            shard_model(model, self.mesh, self.strategy)
        self._params = [p for _, p in model.named_parameters()]
        self._param_names = [n for n, _ in model.named_parameters()]

        m, s = self.mesh, self.strategy
        self._param_shardings = [
            NamedSharding(m, s.param_spec(tuple(p.data.shape), m,
                                          _param_base_spec(p)))
            for p in self._params]
        self._grad_specs = [
            s.grad_spec(tuple(p.data.shape), m, _param_base_spec(p))
            for p in self._params]
        self._opt_state_tree = None
        self._jitted = None
        self._warm_store = None   # enable_warm_start() opt-in
        self._warm_exe = None

    # ----------------------------------------------------------------- build
    def _build(self, batch_ndims):
        m = self.mesh
        names = self._param_names
        grad_specs = self._grad_specs
        acc = self.accumulate_steps
        loss_fn = self.loss_fn
        model = self.model
        opt = self.optimizer

        def loss_of(pvals, *batch):
            pdict = dict(zip(names, pvals))
            out = functional_call(model, pdict, *[Tensor(b) if
                                                  isinstance(b, jax.Array)
                                                  else b for b in batch[:-1]])
            loss = loss_fn(out, jax.tree_util.tree_map(_wrap, batch[-1]))
            return _unwrap(loss)

        if self._recompute is not None and self._recompute.enabled:
            loss_of = self._recompute.wrap(loss_of)

        def grads_of(pvals, *batch):
            loss, grads = jax.value_and_grad(loss_of)(list(pvals), *batch)
            grads = [
                jax.lax.with_sharding_constraint(
                    g, NamedSharding(m, spec))
                for g, spec in zip(grads, grad_specs)]
            return loss, grads

        def step_fn(param_vals, opt_state, lr, step_no, *batch):
            if acc == 1:
                loss, grads = grads_of(param_vals, *batch)
            else:
                # microbatch scan: batch elems have leading dim acc
                def body(carry, micro):
                    l_acc, g_acc = carry
                    l, g = grads_of(param_vals, *micro)
                    return (l_acc + l,
                            [a + b for a, b in zip(g_acc, g)]), None

                zero_g = [jnp.zeros_like(p) for p in param_vals]
                (loss, grads), _ = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), zero_g), batch)
                loss = loss / acc
                grads = [g / acc for g in grads]
            new_params, new_state = opt.apply_gradients(
                list(param_vals), grads, opt_state, lr=lr, step=step_no)
            return loss, new_params, new_state

        donate = (0, 1)
        self._donate = donate
        self._step_fn = step_fn
        self._jitted = jax.jit(
            step_fn, donate_argnums=donate,
            out_shardings=(NamedSharding(m, P()),
                           self._param_shardings, None))
        # warm/AOT path: donation baked only where the backend
        # implements it — deserialized aliasing double-frees donated
        # buffers on CPU (see TrainStep.__init__); the audit keeps the
        # donation intent regardless
        self._aot_donate = donate if jax.default_backend() == "tpu" \
            else ()
        self._aot_jitted = self._jitted if self._aot_donate == donate \
            else jax.jit(
                step_fn, donate_argnums=self._aot_donate,
                out_shardings=(NamedSharding(m, P()),
                               self._param_shardings, None))

    # ------------------------------------------------------------------ call
    def batch_sharding_for(self, leaf) -> NamedSharding:
        """Target input sharding for one batch leaf (rank-determined:
        the leading data dim shards over the dp+sharding axes). This is
        the contract the sharded device prefetcher
        (``io.device_prefetch.prefetch_to_device(loader, step)``)
        places against, so batches arrive committed on exactly the
        shardings ``_shard_batch`` would apply — which then skips."""
        nd = getattr(leaf, "ndim", None)
        if nd is None:
            nd = np.ndim(leaf)
        return NamedSharding(self.mesh, self._batch_leaf_spec(int(nd)))

    @property
    def batch_shardings(self):
        """Callable ``leaf -> NamedSharding`` (alias of
        batch_sharding_for) for prefetchers/loaders."""
        return self.batch_sharding_for

    def _shard_batch(self, arr):
        # the ONE idempotent-placement implementation (skip test +
        # io.host2device counting) lives in io.device_prefetch; lazy
        # import keeps fleet importable without the io package loaded
        from ...io.device_prefetch import place_batch
        sh = NamedSharding(self.mesh, self._batch_leaf_spec(arr.ndim))
        out = place_batch(arr, sh)
        return out._data if isinstance(out, Tensor) else out

    def _ensure_opt_state(self):
        """Seed (or re-load from a restored optimizer) the sharded
        optimizer-state tree."""
        if self._opt_state_tree is not None:
            return
        m, s = self.mesh, self.strategy
        self._opt_state_tree = []
        for p in self._params:
            st = self.optimizer._state.get(_opt_key(p)) \
                or self.optimizer.init_state_for(p)
            st = {k: (jax.device_put(
                v, NamedSharding(m, s.opt_state_spec(
                    tuple(jnp.shape(v)), m, _param_base_spec(p))))
                if v is not None else None)
                for k, v in st.items()}
            self._opt_state_tree.append(st)

    def _prepare(self, batch):
        """Shared by __call__ and lower(): opt state + jit + sharded
        raw batch."""
        if self.abstract:
            raise RuntimeError(
                "DistributedTrainStep(abstract=True) never placed its "
                "parameters/optimizer state on the mesh — it can only "
                "be lower_abstract()'ed, not executed; rebuild with "
                "abstract=False to run steps")
        self._ensure_opt_state()
        if self._jitted is None:
            self._build(tuple(getattr(b, "ndim", 0) for b in batch))
        return tuple(
            jax.tree_util.tree_map(
                lambda t: self._shard_batch(_unwrap(t)), b,
                is_leaf=lambda t: isinstance(t, Tensor))
            for b in batch)

    def lower(self, *batch):
        """jax Lowered for the step on these example inputs — the
        auto-parallel tuner compiles it per candidate mesh and scores
        the resulting program (tuner.py); also usable for AOT caching."""
        raw_batch = self._prepare(batch)
        return self._jitted.lower(
            [p._data for p in self._params], self._opt_state_tree,
            np.float32(self.optimizer.get_lr()),
            np.int32(self.optimizer._step_count + 1), *raw_batch)

    def _batch_leaf_spec(self, nd: int) -> P:
        lead = 1 if self.accumulate_steps > 1 else 0
        parts = [None] * nd
        if nd > lead:
            parts[lead] = DATA_AXES
        return P(*parts)

    def _abstract_operands(self, *batch):
        """ShapeDtypeStruct operands for step_fn — shapes, dtypes AND
        shardings, exactly what the compiled program runs with. The ONE
        construction shared by lower_abstract() and audit(), so the
        audited program can never drift from the lowered one. `batch`
        leaves may be arrays, Tensors, or ShapeDtypeStructs — only
        shape/dtype are read."""
        m, s = self.mesh, self.strategy
        p_avals = [jax.ShapeDtypeStruct(tuple(p.data.shape), p.data.dtype,
                                        sharding=sh)
                   for p, sh in zip(self._params, self._param_shardings)]
        opt_avals = []
        for p in self._params:
            st = jax.eval_shape(self.optimizer.init_state_for, p._data)
            opt_avals.append({
                k: (jax.ShapeDtypeStruct(
                    tuple(v.shape), v.dtype,
                    sharding=NamedSharding(m, s.opt_state_spec(
                        tuple(v.shape), m, _param_base_spec(p))))
                    if v is not None else None)
                for k, v in st.items()})
        repl = NamedSharding(m, P())
        lr_aval = jax.ShapeDtypeStruct((), np.float32, sharding=repl)
        no_aval = jax.ShapeDtypeStruct((), np.int32, sharding=repl)

        def leaf_aval(t):
            x = _unwrap(t)
            nd = len(x.shape)
            return jax.ShapeDtypeStruct(
                tuple(x.shape), x.dtype,
                sharding=NamedSharding(m, self._batch_leaf_spec(nd)))

        batch_avals = tuple(
            jax.tree_util.tree_map(
                leaf_aval, b, is_leaf=lambda t: isinstance(t, Tensor))
            for b in batch)
        return p_avals, opt_avals, lr_aval, no_aval, batch_avals

    def lower_abstract(self, *batch):
        """jax Lowered built from abstract (ShapeDtypeStruct) operands:
        no parameter, optimizer-state, or batch buffer is ever placed
        on the mesh, so meshes far larger than host memory compile-plan
        fine."""
        if self._jitted is None:
            self._build(None)
        p_avals, opt_avals, lr_aval, no_aval, batch_avals = \
            self._abstract_operands(*batch)
        return self._jitted.lower(p_avals, opt_avals, lr_aval, no_aval,
                                  *batch_avals)

    def cost_analysis(self, *batch):
        """XLA cost analysis of the compiled distributed step."""
        ca = self.lower(*batch).compile().cost_analysis()
        return ca[0] if isinstance(ca, (list, tuple)) else ca

    def audit(self, *batch, donate=(0, 1), **audit_kw):
        """Static audit of the sharded step on abstract operands (works
        for ``abstract=True`` plan-only steps too — nothing is placed
        on the mesh). ``donate`` defaults to what the step donates:
        params + opt state."""
        from ...analysis import audit as _audit
        if self._jitted is None:
            self._build(None)
        p_avals, opt_avals, lr_aval, no_aval, batch_avals = \
            self._abstract_operands(*batch)
        audit_kw.setdefault("name", "DistributedTrainStep.step_fn")
        with self.mesh:
            return _audit(self._step_fn, p_avals, opt_avals, lr_aval,
                          no_aval, *batch_avals, donate=donate,
                          **audit_kw)

    def enable_warm_start(self, store=None):
        """Opt-in executable persistence for the sharded step (same
        contract as ``TrainStep.enable_warm_start``): the first call
        lowers and loads a serialized executable from the store —
        keyed on the mesh axes too, so a resize can never replay the
        wrong program — falling back to (and persisting) a fresh
        compile on a cold store."""
        from ...jit import compile_cache
        self._warm_store = store if store is not None \
            else compile_cache.default_store()
        return self

    def _mesh_signature(self):
        return tuple(zip(self.mesh.axis_names,
                         self.mesh.devices.shape))

    def _warm_signature(self, args):
        """Traceless manifest key for the sharded step (same contract
        as TrainStep._warm_signature) — the mesh axes and sharding
        strategy join the key, so a resized mesh or changed ZeRO stage
        can never resolve to a stale executable."""
        from ...jit import compile_cache
        sig = compile_cache.network_signature(self.model)
        loss_sig = compile_cache.callable_signature(self.loss_fn)
        opt_src = compile_cache.source_hash(type(self.optimizer))
        flags = repr((self.accumulate_steps, self._recompute))
        if sig is None or loss_sig is None or opt_src is None \
                or "0x" in flags:
            return None
        sig.update(
            program=("DistributedTrainStep",), loss=loss_sig,
            opt=(type(self.optimizer).__qualname__, opt_src,
                 compile_cache.scalar_signature(self.optimizer)),
            strategy=(type(self.strategy).__qualname__,
                      compile_cache.scalar_signature(self.strategy)),
            flags=flags, mesh=self._mesh_signature(),
            operands=compile_cache.aval_signature(args))
        return sig

    def __call__(self, *batch):
        # host side only, as TrainStep: prepare, dispatch
        with flight_recorder.span("train.step") as sp:
            return self._call(sp, batch)

    def _call(self, sp, batch):
        params = self._params
        raw_batch = self._prepare(batch)
        lr = self.optimizer.get_lr()
        self.optimizer._step_count += 1
        args = ([p._data for p in params], self._opt_state_tree,
                np.float32(lr), np.int32(self.optimizer._step_count),
                *raw_batch)
        if self._warm_store is not None and self._warm_exe is None:
            from ...core import monitor
            try:
                self._warm_exe = compile_cache.build_or_load(
                    self._warm_signature(args),
                    lambda: self._aot_jitted.lower(*args),
                    store=self._warm_store,
                    extra=dict(kind="DistributedTrainStep",
                               donation=self._aot_donate,
                               mesh=self._mesh_signature()),
                    label="fleet.train_step")
            except Exception as e:
                # never let persistence break a training step
                monitor.record_swallowed(
                    "jit.compile_cache.fleet_warm", e)
            self._warm_store = None  # warmed once; drift falls back
            sp.set(compiled=1)
        if self._warm_exe is not None:
            try:
                loss, new_vals, self._opt_state_tree = \
                    self._warm_exe(*args)
            except (TypeError, ValueError) as e:
                from ...core import monitor
                monitor.record_swallowed(
                    "jit.compile_cache.fleet_warm_step", e)
                self._warm_exe = None
        if self._warm_exe is None:
            cache_of = _RetraceTracker._cache_of
            pre = cache_of(self._jitted) if flight_recorder.enabled \
                else None
            hits = compile_cache.persistent_cache_hits() \
                if pre is not None else 0
            loss, new_vals, self._opt_state_tree = self._jitted(*args)
            if pre is not None and (cache_of(self._jitted) or 0) > pre:
                _note_built(sp, "fleet.train_step", hits)
        for p, v in zip(params, new_vals):
            p._data = v
        for p, st in zip(params, self._opt_state_tree):
            self.optimizer._state[_opt_key(p)] = st
        from ...optimizer.lr import LRScheduler
        if isinstance(self.optimizer._lr, LRScheduler) and \
                self.optimizer._lr._step_each_iter:
            self.optimizer._lr.step()
        return _wrap(loss)

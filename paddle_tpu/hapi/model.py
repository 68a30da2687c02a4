"""hapi.Model: the Keras-like high-level train/eval/predict loop.

Reference analog: python/paddle/hapi/model.py:1009 (Model.fit :1149,
evaluate, predict, save/load, prepare) — minus the static-graph adapter
(capture is jax.jit here, always on: train_batch goes through the fused
TrainStep, eval/predict through a jitted forward).
"""
from __future__ import annotations

import collections
import os
import pickle
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from .. import framework_io
from ..core import flight_recorder, goodput, monitor, slo
from ..core.tensor import Tensor
from ..io.dataloader import DataLoader
from ..io.dataset import Dataset
from ..jit.api import TrainStep, to_static
from ..metric import Metric
from ..nn.layer import Layer
from .callbacks import (Callback, CallbackList, EarlyStopping,
                        LRSchedulerCallback, ModelCheckpoint, ProgBarLogger)


def _to_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))  # lint: host-sync-ok (host input prep)


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class AsyncScalarFetcher:
    """Bounded lag window between device-side scalar production and
    host-side consumption — the non-blocking train loop's core.

    ``float(loss)`` after every step drains the device dispatch queue:
    the host stalls until step N finishes before it can even *launch*
    step N+1, so H2D transfer, host-side batching and device compute
    never overlap. Instead ``push(step, loss)`` enqueues the on-device
    scalar and returns the values that have matured out of a ``lag``-
    step window (default 2, ``PADDLE_ASYNC_STEPS``; 0 restores fully
    synchronous reads). By the time a value is popped the device has
    had ``lag`` steps of runway, so the transfer is almost always a
    ready-buffer copy, not a stall — ``train.loss_fetches`` counts
    every read-back and ``train.host_syncs`` counts the subset that
    actually blocked, which the host-sync regression gate bounds.

    ``drain()`` flushes the window in order (epoch end: no value is
    dropped or reordered, it is only observed up to ``lag`` steps
    late); ``sync()`` blocks until every in-flight value is computed
    WITHOUT consuming it (the emergency-save barrier: a checkpoint
    taken after ``sync()`` reflects fully-executed steps, never a
    half-dispatched one)."""

    def __init__(self, lag: Optional[int] = None, record: bool = True):
        if lag is None:
            env = os.environ.get("PADDLE_ASYNC_STEPS", "").strip()
            try:
                lag = int(env) if env else 2
            except ValueError:
                lag = 2
        self.lag = max(0, int(lag))
        # record=False: don't touch the train.loss_fetches/host_syncs
        # counters — those name the TRAIN loop's pipeline contract; the
        # eval loop reuses the window mechanics but must not pollute
        # the gated metric
        self.record = bool(record)
        self._window: collections.deque = collections.deque()

    def __len__(self):
        return len(self._window)

    @staticmethod
    def _ready(value) -> bool:
        arr = getattr(value, "_data", value)
        try:
            return bool(arr.is_ready())  # lint: host-sync-ok (non-blocking probe)
        except AttributeError:
            return True  # plain host scalar: nothing to wait for

    def push(self, step: int, value):
        """Enqueue step's on-device scalar; return the [(step, float)]
        that matured out of the lag window (possibly empty)."""
        self._window.append((step, value))
        out = []
        while len(self._window) > self.lag:
            s, v = self._window.popleft()
            if self.record and monitor.enabled:
                monitor.record_loss_fetch(not self._ready(v))
            out.append((s, float(v)))  # lint: host-sync-ok (bounded lag window)
        return out

    def drain(self):
        """Flush the whole window in push order. One drain is ONE sync
        barrier: at most one blocking read-back is charged to
        ``train.host_syncs`` however many values are pending."""
        out = []
        blocked = False
        while self._window:
            s, v = self._window.popleft()
            if self.record and monitor.enabled:
                b = not self._ready(v)
                monitor.record_loss_fetch(b and not blocked)
                blocked = blocked or b
            out.append((s, float(v)))  # lint: host-sync-ok (counted drain barrier)
        return out

    def sync(self):
        """Block until every pending value is computed, without
        consuming any — the device has caught up with the host."""
        for _, v in self._window:
            arr = getattr(v, "_data", v)
            try:
                arr.block_until_ready()
            except AttributeError:
                pass


class Model:
    """Wraps a Layer with train/eval/predict loops (paddle.Model API)."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._train_step: Optional[TrainStep] = None
        self._eval_fn = None
        self._save_dir = None
        self._fit_progress = None  # live {epoch, step, loader} during fit

    # ------------------------------------------------------------ prepare
    def prepare(self, optimizer=None, loss=None, metrics=None):
        self._optimizer = optimizer
        if isinstance(loss, Layer):
            self._loss = lambda out, lbl: loss(out, lbl)
        else:
            self._loss = loss
        self._metrics = _as_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metrics must be paddle.metric.Metric, "
                                f"got {type(m)}")
        if optimizer is not None and loss is not None:
            self._train_step = TrainStep(self.network, optimizer,
                                         self._loss)
        self._eval_fn = to_static(self.network)
        self._eval_step_jit = None  # lazily-built jitted (out, loss) step
        self._eval_loss_eager = False  # loss not jax-traceable: eager path
        return self

    # ------------------------------------------------------- batch methods
    def train_batch(self, inputs, labels):
        """Run one fused train step and return the ON-DEVICE loss (a
        scalar Tensor). The call does not wait for the step to finish —
        ``float(loss)`` forces the host transfer when the value is
        actually needed. fit() reads losses through a lagged
        AsyncScalarFetcher so the device queue stays full."""
        if self._train_step is None:
            raise RuntimeError("call prepare(optimizer, loss) first")
        self.network.train()
        inputs = [_to_tensor(x) for x in _as_list(inputs)]
        labels = [_to_tensor(x) for x in _as_list(labels)]
        return self._train_step(*inputs, *labels)

    def _build_eval_step(self):
        """Jit ONE program computing (outputs, loss): the loss no longer
        runs eagerly outside the compiled eval fn, and the returned loss
        is an on-device scalar read back asynchronously (same contract
        as train_batch). Parameters are passed as operands re-read every
        call, so optimizer updates between evals are seen without a
        retrace."""
        import jax
        from ..jit.api import _RetraceTracker, _unwrap, _wrap, \
            functional_call
        net, loss_fn = self.network, self._loss

        @jax.jit
        def jitted(state_vals, arg_vals, label_val):
            names = jitted._state_names
            out = functional_call(net, dict(zip(names, state_vals)),
                                  *arg_vals)
            loss = loss_fn(out, jax.tree_util.tree_map(_wrap, label_val))
            unw = jax.tree_util.tree_map(
                _unwrap, out, is_leaf=lambda x: isinstance(x, Tensor))
            return unw, _unwrap(loss)

        # state walked ONCE here, not per eval batch (the TrainStep
        # _params_cache fix, applied to eval): Tensor objects are
        # mutated in place by optimizer/set_state_dict, so re-reading
        # ._data each call sees fresh values without a re-walk
        state = net.state_dict()
        jitted._state_names = list(state.keys())
        self._eval_state_cache = list(state.values())
        self._eval_step_jit = jitted
        self._eval_tracker = _RetraceTracker()

    def _eval_batch_eager(self, inputs, labels):
        """Pre-pipeline eval path: compiled forward, loss computed
        eagerly on its outputs — the fallback for user losses that are
        not jax-traceable (host-side ``.numpy()``/``float()``)."""
        out = self._eval_fn(*inputs)
        return out, self._loss(out, labels[0])

    def eval_batch(self, inputs, labels):
        self.network.eval()
        inputs = [_to_tensor(x) for x in _as_list(inputs)]
        labels = [_to_tensor(x) for x in _as_list(labels)]
        if self._loss is None:
            return self._eval_fn(*inputs), None
        if getattr(self, "_eval_loss_eager", False):
            return self._eval_batch_eager(inputs, labels)
        if getattr(self, "_eval_step_jit", None) is None:
            self._build_eval_step()
        from ..jit.api import _wrap
        jitted = self._eval_step_jit
        state_vals = tuple(t._data for t in self._eval_state_cache)
        arg_vals = tuple(t._data for t in inputs)
        label_val = labels[0]._data
        pre = self._eval_tracker.pre(jitted)
        try:
            out, loss = jitted(state_vals, arg_vals, label_val)
        except (jax.errors.JAXTypeError, TypeError):
            # the user's loss callable does host-side work on tracers
            # (eval-only Models could always do that: the loss used to
            # run eagerly outside the compiled fn) — permanently fall
            # back to the eager path for this Model
            self._eval_loss_eager = True
            self._eval_step_jit = None
            return self._eval_batch_eager(inputs, labels)
        self._eval_tracker.observe(jitted, (state_vals, arg_vals,
                                            label_val), pre)
        return jax.tree_util.tree_map(_wrap, out), Tensor(loss)

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [_to_tensor(x) for x in _as_list(inputs)]
        return self._eval_fn(*inputs)

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        """Autoregressive decoding through the KV-cache generation
        subsystem: one jitted prefill + one jitted decode step, one
        device dispatch per generated token. The wrapped network must
        implement the cache protocol (``forward(input_ids,
        use_cache=..., cache=...)`` returning (logits, cache) — e.g.
        ``models.gpt.GPTForCausalLM``). Sampling options
        (do_sample/temperature/top_k/top_p/eos_token_id/seed/...) and
        speculative decoding (``speculative="ngram"`` for model-free
        prompt-lookup drafting, ``speculative="draft"`` with
        ``draft_model=`` — up to draft-k+1 tokens per dispatch, greedy
        outputs bitwise-unchanged) are forwarded to
        ``paddle_tpu.generation.generate``. Returns the generated ids
        only, [batch, max_new_tokens] int32."""
        from ..generation.api import generate as _generate
        return _generate(self.network, input_ids, max_new_tokens,
                         **kwargs)

    # -------------------------------------------------------------- loops
    def _loader(self, data, batch_size, shuffle):
        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle)
        return data  # any iterable of (inputs, labels)

    def fit(self, train_data=None, eval_data=None, batch_size=1,
            epochs=1, eval_freq=1, log_freq=10, save_dir=None,
            save_freq=1, verbose=1, shuffle=True, callbacks=None,
            anomaly_guard=None, resume=None):
        """≈ hapi model.py:1149 — epochs over train_data with optional
        periodic eval, checkpointing, logging, early stopping.

        The loop is NON-BLOCKING: train_batch returns the on-device
        loss and a bounded AsyncScalarFetcher reads values back with a
        lag of ``PADDLE_ASYNC_STEPS`` steps (default 2, 0 = fully
        synchronous), so the host keeps the device dispatch queue full
        instead of stalling on ``float(loss)`` every step. Callbacks
        and the anomaly guard observe each loss up to that many steps
        after its batch was launched; the window drains at epoch end
        (and before any emergency save), so no loss is ever dropped or
        reordered.

        ``anomaly_guard``: resilience.AnomalyGuard instance, True for a
        default one, or None (also enabled by PADDLE_ANOMALY_GUARD=1) —
        non-finite losses skip the batch (the TrainStep keeps params
        unchanged in-jit) and N consecutive anomalies restore network +
        optimizer from the last good in-memory snapshot. The loop also
        polls the active resilience.GracefulShutdown each batch, so a
        preemption lands as emergency-save + exit(ELASTIC_EXIT_CODE) at
        a batch boundary.

        ``resume``: True (with ``save_dir``) or an explicit checkpoint
        prefix — reload params/optimizer from the emergency checkpoint
        a preempted fit wrote and continue EXACTLY where it stopped:
        the saved train state ({prefix}.pdstate) carries the epoch,
        global step and the DataLoader's cursor + sampler state, so a
        mid-epoch preemption replays only the remaining batches of the
        interrupted epoch (at most one step redone). Missing files mean
        a fresh start, so first launch and relaunch share one call."""
        # the goodput ledger: every wall second of this fit lands in
        # exactly one bucket (compute/compile/data_stall/checkpoint/
        # preemption_recovery/idle — the train.goodput.* family).
        # Started FIRST — before even the resilience import, whose
        # first-use cost is real fit wall time — so the wall it
        # decomposes is the fit the caller measured: loader
        # construction (worker spawn, first io imports) is
        # input-pipeline setup — data_stall — and the resume restore
        # is preemption recovery
        ledger = goodput.GoodputLedger("train").start()
        from ..distributed import resilience
        with ledger.timed("data_stall"):
            loader = self._loader(train_data, batch_size, shuffle)
            eval_loader = self._loader(eval_data, batch_size, False)
        self._save_dir = save_dir
        start_epoch = 0
        if resume:
            prefix = resume if isinstance(resume, str) else (
                os.path.join(save_dir, "emergency") if save_dir else None)
            if prefix is None:
                raise ValueError("resume=True requires save_dir "
                                 "(or pass an explicit prefix)")
            with ledger.timed("preemption_recovery"):
                start_epoch = self._load_resume(prefix, loader)

        guard = self._resolve_anomaly_guard(anomaly_guard, resilience)
        if resume and self._train_step is not None:
            # relaunch warm path (opt-in by the resume request): with an
            # executable store active (enable_compile_cache) the
            # first step loads the
            # serialized fused-step executable instead of recompiling —
            # after the guard resolution above, which may have rebuilt
            # the TrainStep
            from ..jit import compile_cache
            if compile_cache.default_store() is not None:
                self._train_step.enable_warm_start()

        cbs = CallbackList([ProgBarLogger(log_freq, verbose=verbose)]
                           + _as_list(callbacks))
        if save_dir:
            cbs.append(ModelCheckpoint(save_freq, save_dir))
        cbs.append(LRSchedulerCallback())
        cbs.set_model(self)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbs.set_params({"epochs": epochs, "steps": steps,
                        "verbose": verbose})

        cbs.on_train_begin()
        if guard is not None:
            self._take_good_snapshot()
        try:
            with ledger:   # ambient: deep saves charge checkpoint/
                #            preemption_recovery without plumbing
                self._fit_loop(loader, eval_loader, epochs, eval_freq,
                               cbs, guard, resilience, start_epoch,
                               ledger)
        except BaseException as abort:
            # uncaught exception in fit(): leave the black box before
            # anything else — the last steps, compiles, anomalies and
            # loader events explain the crash. SystemExit is the
            # GracefulShutdown preemption path, which already dumped.
            if not isinstance(abort, SystemExit):
                flight_recorder.record(
                    "fit.crash",
                    error=f"{type(abort).__name__}: {abort}")
                flight_recorder.auto_dump("fit_crash")
            # on_train_end will not run: let callbacks release what
            # on_train_begin acquired (emergency-saver registrations,
            # the metrics registry, ...) before the abort propagates.
            # Cleanup must never mask the original failure — a broken
            # or duck-typed callback without the hook is swallowed.
            try:
                cbs.on_train_abort()
            except Exception as e:
                from ..core import monitor
                monitor.record_swallowed("fit.on_train_abort", e)
            raise
        # the closed ledger's final decomposition (buckets sum to wall
        # — the tier-1 invariant), for callers without the registry on
        self.goodput_summary = ledger.snapshot()
        return self

    def _consume_loss(self, step, loss, guard, cbs, losses):
        """Host-side handling of ONE matured loss value (float): the
        anomaly guard and the batch-end callbacks observe losses here,
        ``lag`` steps after the step that produced them was launched."""
        if flight_recorder.enabled:
            # ...and train.step_end marks the last loss that MATURED
            # out of the async window (up to lag steps behind dispatch)
            flight_recorder.record("train.step_end", step=step,
                                   loss=float(loss))  # lint: host-sync-ok (loss already matured to a host float)
        if guard is not None and not guard.observe(loss):
            # anomaly: loss not recorded, params were kept
            # unchanged in-jit (skip_nonfinite TrainStep)
            cbs.on_train_batch_end(step, {"loss": loss,
                                          "skipped_batch": True})
        else:
            losses.append(loss)
            cbs.on_train_batch_end(step, {"loss": loss})

    def _fit_loop(self, loader, eval_loader, epochs, eval_freq, cbs,
                  guard, resilience, start_epoch=0, ledger=None):
        stop = False
        global_step = 0
        if ledger is None:   # direct callers (tests) get a live one
            ledger = goodput.GoodputLedger("train").start()
        # the lagged loss window: train_batch returns the on-device
        # scalar, the fetcher reads it back K steps later so the host
        # never drains the device dispatch queue mid-epoch
        fetcher = AsyncScalarFetcher()
        # live progress the emergency saver (ModelCheckpoint) snapshots:
        # epoch, step, and the loader whose state_dict pins the batch
        # cursor — together the exact mid-epoch resume point. The
        # fetcher rides along so _train_state can sync the in-flight
        # window before an emergency save (the saved step is always a
        # fully-executed one).
        progress = {"epoch": start_epoch, "step": 0, "loader": loader,
                    "fetcher": fetcher}
        self._fit_progress = progress
        for epoch in range(start_epoch, epochs):
            progress["epoch"] = epoch
            cbs.on_epoch_begin(epoch)
            losses = []
            batches = iter(loader)
            step = -1
            while True:
                # input-pipeline wait is the data_stall bucket: with a
                # prefetching loader this is near zero; a slow disk or
                # a dead worker shows up HERE, not as fake compute
                t_fetch = time.perf_counter()
                try:
                    batch = next(batches)
                except StopIteration:
                    ledger.charge("data_stall",
                                  time.perf_counter() - t_fetch)
                    break
                ledger.charge("data_stall",
                              time.perf_counter() - t_fetch)
                step += 1
                cbs.on_train_batch_begin(step)
                inputs, labels = self._split_batch(batch)
                if flight_recorder.enabled:
                    # black-box step boundary: a post-mortem dump shows
                    # the last step the host DISPATCHED...
                    flight_recorder.record("train.step_begin",
                                           step=global_step + 1,
                                           epoch=epoch)
                retraces0 = monitor.retrace_count()
                t_step = time.perf_counter()
                loss = self.train_batch(inputs, labels)
                global_step += 1
                progress["step"] = global_step
                for s, val in fetcher.push(step, loss):
                    self._consume_loss(s, val, guard, cbs, losses)
                # a dispatch during which a retrace happened spent its
                # wall time tracing + XLA-compiling, not computing:
                # that window is the compile bucket (the always-on
                # retrace census works with the registry disabled)
                dt_step = time.perf_counter() - t_step
                ledger.charge(
                    "compile" if monitor.retrace_count() > retraces0
                    else "compute", dt_step)
                # the per-step wall series the fleet straggler detector
                # diffs per rank and the step-time SLO evaluates; the
                # watchtower tick samples/evaluates at most once per
                # ring period (fast path: one float compare)
                monitor.record_train_step_time(dt_step)
                slo.tick()
                # preemption lands here: emergency save + exit(101)
                resilience.poll(global_step)
                if any(getattr(cb, "stopped", False)
                       for cb in cbs.callbacks):
                    stop = True  # e.g. TerminateOnNaN
                    break
            # epoch end drains the lag window: every loss is observed,
            # in order, before epoch logs / checkpoints / eval run
            for s, val in fetcher.drain():
                self._consume_loss(s, val, guard, cbs, losses)
            if not stop and any(getattr(cb, "stopped", False)
                                for cb in cbs.callbacks):
                stop = True  # a drained tail loss tripped a callback
            if stop:
                # a mid-epoch stop (NaN loss) skips the epoch tail:
                # no checkpoint of poisoned weights, no wasted eval
                break
            logs = {"loss": float(np.mean(losses))  # lint: host-sync-ok (host floats)
                    if losses else None}
            # flush the ledger window BEFORE the epoch-end callbacks so
            # MetricsCallback reads this epoch's goodput, not last's
            ledger.flush()
            cbs.on_epoch_end(epoch, logs)
            if guard is not None:
                self._take_good_snapshot()

            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                self._run_eval(eval_loader, cbs)
            # any callback may request a stop (EarlyStopping, ...)
            if any(getattr(cb, "stopped", False)
                   for cb in cbs.callbacks):
                break
        cbs.on_train_end()

    def _run_eval(self, loader, cbs):
        cbs.on_eval_begin()
        for m in self._metrics:
            m.reset()
        losses = []
        # same lag-window contract as the train loop: eval_batch
        # returns the on-device scalar, callbacks observe each loss as
        # a FLOAT up to K steps late, and the window drains (one
        # barrier) at eval end — never a per-batch blocking read-back.
        # record=False: train.loss_fetches/host_syncs stay a pure
        # train-loop contract
        fetcher = AsyncScalarFetcher(record=False)
        for step, batch in enumerate(loader):
            cbs.on_eval_batch_begin(step)
            inputs, labels = self._split_batch(batch)
            out, loss = self.eval_batch(inputs, labels)
            for m in self._metrics:
                if hasattr(m, "compute"):
                    m.update(m.compute(out, _as_list(labels)[0]))
                else:
                    m.update(out, _as_list(labels)[0])
            if loss is None:
                cbs.on_eval_batch_end(step, {"loss": None})
                continue
            for s, val in fetcher.push(step, loss):
                losses.append(val)
                cbs.on_eval_batch_end(s, {"loss": val})
        for s, val in fetcher.drain():
            losses.append(val)
            cbs.on_eval_batch_end(s, {"loss": val})
        logs = {}
        if losses:
            logs["loss"] = float(np.mean(losses))  # lint: host-sync-ok (host floats)
        for m in self._metrics:
            logs[m.name()] = m.accumulate()
        cbs.on_eval_end(logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, verbose=1, callbacks=None):
        loader = self._loader(eval_data, batch_size, False)
        cbs = CallbackList([ProgBarLogger(verbose=verbose)]
                           + _as_list(callbacks))
        cbs.set_model(self)
        cbs.set_params({"verbose": verbose})
        return self._run_eval(loader, cbs)

    def predict(self, test_data, batch_size=1, stack_outputs=True):
        loader = self._loader(test_data, batch_size, False)
        outs = []
        for batch in loader:
            inputs = batch[0] if isinstance(batch, (list, tuple)) and \
                len(batch) >= 1 else batch
            out = self.predict_batch(inputs)
            # predict() hands host arrays back by contract
            out = out.numpy() if isinstance(out, Tensor) else out  # lint: host-sync-ok
            outs.append(np.asarray(out))  # lint: host-sync-ok (already host)
        if stack_outputs and outs:
            return [np.concatenate(outs, axis=0)]
        return [outs]

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, (list, tuple)) and len(batch) == 2:
            return batch[0], batch[1]
        if isinstance(batch, (list, tuple)) and len(batch) > 2:
            return list(batch[:-1]), batch[-1]
        raise ValueError("batch must be (inputs, labels)")

    # ------------------------------------------------------------- params
    def parameters(self):
        return self.network.parameters()

    def summary(self, input_size=None):
        total = sum(int(np.prod(p.shape)) for p in
                    self.network.parameters())
        lines = [f"{'Layer':<40}{'Params':>12}", "-" * 52]
        for name, sub in self.network.named_sublayers():
            n = sum(int(np.prod(p.shape))
                    for p in sub.parameters(include_sublayers=False))
            if n:
                lines.append(f"{name:<40}{n:>12}")
        lines.append("-" * 52)
        lines.append(f"{'Total params':<40}{total:>12}")
        text = "\n".join(lines)
        print(text)
        return {"total_params": total}

    # --------------------------------------------------------- resilience
    def _resolve_anomaly_guard(self, anomaly_guard, resilience):
        """fit()'s anomaly_guard arg -> AnomalyGuard or None. True (or
        PADDLE_ANOMALY_GUARD=1 in the env) builds a default guard wired
        to restore from the last good snapshot; a passed guard without a
        restore_fn gets the same wiring. With a guard active, the
        TrainStep is rebuilt with the in-jit non-finite skip."""
        guard = anomaly_guard
        if guard is None:
            env = os.environ.get("PADDLE_ANOMALY_GUARD", "").strip()
            if env and env.lower() not in ("0", "false", "off"):
                guard = True
        if guard is True:
            guard = resilience.AnomalyGuard(
                restore_fn=self._restore_last_good)
        elif guard is not None:
            # wire (or RE-wire) the auto restore to THIS model: a guard
            # reused across models must not roll back the previous one.
            # A restore_fn the caller set explicitly is left alone.
            if getattr(guard, "_auto_wired", False):
                guard.restore_fn = None
            if guard.restore_fn is None:
                guard.restore_fn = self._restore_last_good
                guard._auto_wired = True
        if guard is not None and self._train_step is not None and \
                not self._train_step._skip_nonfinite:
            self._train_step = TrainStep(self.network, self._optimizer,
                                         self._loss, skip_nonfinite=True)
        return guard

    def _train_state(self):
        """The resume point of a fit() in flight: epoch, global step,
        and the DataLoader's cursor + sampler state. ModelCheckpoint
        writes this next to the emergency params so a relaunched
        ``fit(resume=True)`` continues mid-epoch. None outside fit()."""
        p = self._fit_progress
        if p is None:
            return None
        fetcher = p.get("fetcher")
        if fetcher is not None:
            # barrier: every launched step has finished on device, so
            # the saved (epoch, step, loader cursor) names a fully-
            # executed step — an emergency save never checkpoints
            # params mid-dispatch or a stale loss window
            fetcher.sync()
        st = {"epoch": int(p["epoch"]), "step": int(p["step"])}
        ld = p.get("loader")
        if ld is not None and hasattr(ld, "state_dict"):
            st["loader"] = ld.state_dict()
        return st

    def _load_resume(self, prefix, loader) -> int:
        """Restore {prefix}.pdparams/.pdopt + {prefix}.pdstate and
        rewind the loader; returns the epoch to start from. Missing
        files mean a fresh start (0)."""
        if not os.path.exists(prefix + ".pdparams"):
            return 0
        self.load(prefix)
        state_path = prefix + ".pdstate"
        if not os.path.exists(state_path):
            return 0
        ts = framework_io.load(state_path)
        epoch = int(ts.get("epoch", 0))
        ld_state = ts.get("loader")
        if ld_state and loader is not None \
                and hasattr(loader, "load_state_dict"):
            # cursor > 0: re-enter the interrupted epoch, the rewound
            # loader yields only its remaining batches; cursor 0 means
            # the epoch boundary was reached: next epoch
            mid_epoch = loader.load_state_dict(ld_state) > 0
            return epoch if mid_epoch else epoch + 1
        # no loader cursor to pin the position (stateless loader, or
        # the state predates loader capture): the preemption may have
        # landed mid-epoch, so conservatively redo the interrupted
        # epoch (<=1 epoch redone) rather than skip its remainder
        return epoch

    def _take_good_snapshot(self):
        """Host-memory copy of network + optimizer state — what the
        anomaly guard restores when a non-finite streak poisons a run."""
        net = {k: np.array(v.numpy(), copy=True)  # lint: host-sync-ok (anomaly-guard snapshot)
               for k, v in self.network.state_dict().items()}
        opt = self._optimizer.state_dict() \
            if self._optimizer is not None else None
        self._last_good = (net, opt)

    def _restore_last_good(self):
        """Roll network + optimizer back to the last good snapshot (the
        anomaly guard's restore_fn)."""
        snap = getattr(self, "_last_good", None)
        if snap is None:
            return
        net, opt = snap
        self.network.set_state_dict(net)
        if opt is not None and self._optimizer is not None:
            self._optimizer.set_state_dict(opt)
        if self._train_step is not None:
            # drop the fused step's cached opt-state tree so the next
            # call re-seeds from the restored optimizer state
            self._train_step._opt_state_tree = None

    # --------------------------------------------------------------- save
    def save(self, path: str, training: bool = True):
        """{path}.pdparams (+ {path}.pdopt when training) — the reference's
        save layout (hapi model.py save)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        framework_io.save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            framework_io.save(self._optimizer.state_dict(),
                              path + ".pdopt")

    def load(self, path: str, skip_mismatch: bool = False, reset_optimizer: bool = False):
        state = framework_io.load(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(framework_io.load(opt_path))
        return self

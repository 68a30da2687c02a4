"""Sharded, async, topology-aware checkpointing (orbax-backed).

Reference analogs:
- paddle.save/load object tier → framework_io.py (pickle).
- Sharded/async distributed tier (this module): the reference's
  per-stage/per-rank shard saves (group_sharded utils,
  hybrid_parallel_pp_save_load tests) become orbax OCDBT checkpoints of
  the GLOBAL arrays — every host writes only its addressable shards,
  restore re-assembles under ANY new mesh/sharding.
- Cross-strategy resharding (auto_parallel/converter.py: reshard a ckpt
  saved under one parallel strategy into another) → `with_shardings` on
  restore: orbax places each array straight into the requested
  NamedSharding, so dp-saved → tp-restored "conversion" is a placement
  argument, not a data shuffle pass.
- Auto-checkpoint (fluid/incubate/checkpoint/auto_checkpoint.py:72:
  epoch-granular transparent resume) → CheckpointManager(max_to_keep,
  save_interval) + `resume()`.
- Fault tolerance (this PR's resilience layer): every committed step
  carries a `_PADDLE_COMMIT` marker recording the tree's leaf
  shapes/dtypes; `restore()` validates it and falls back step-by-step
  (latest → previous → ...) past truncated or uncommitted checkpoints,
  reporting every skipped step through `core.monitor`.
  `save_on_preemption()` registers the manager with the active
  `resilience.GracefulShutdown` so a SIGTERM triggers a synchronous
  emergency save before the elastic relaunch.
- Input-pipeline state (this PR): `DataLoader.state_dict()` trees
  (batch cursor + sampler epoch/seed — plain int leaves) ride inside
  the same save/restore trees; orbax round-trips them and
  `DataLoader.load_state_dict` coerces the restored 0-d leaves, so a
  per-step checkpoint pins the exact mid-epoch resume point alongside
  model and optimizer state.
"""
from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..core import flight_recorder, monitor
from ..core.tensor import Tensor

COMMIT_MARKER = "_PADDLE_COMMIT"


class CheckpointCorruption(RuntimeError):
    """No restorable checkpoint: every candidate step failed commit
    validation or raised during restore."""


def _flatten_tree(tree) -> Dict[str, Any]:
    """Flat {'/'-joined path: leaf} view of a dict/list tree — the one
    traversal both the commit-marker writer and validate() key off, so
    their paths can never drift apart."""
    out: Dict[str, Any] = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else str(k))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{prefix}/{i}" if prefix else str(i))
        else:
            out[prefix] = node

    walk(tree, "")
    return out


def _leaf_metadata(tree) -> Dict[str, Dict[str, Any]]:
    """Flat {path: {shape, dtype}} map of the raw state tree — the
    structural contract a restore validates against."""
    out: Dict[str, Dict[str, Any]] = {}
    for path, leaf in _flatten_tree(tree).items():
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            out[path] = {"shape": list(leaf.shape),
                         "dtype": str(np.dtype(leaf.dtype))}
        else:
            out[path] = {"shape": None, "dtype": type(leaf).__name__}
    return out


def _to_raw_tree(obj):
    """Tensors/np → jax arrays; containers preserved; scalars pass."""
    if isinstance(obj, Tensor):
        return obj._data
    if isinstance(obj, np.generic):
        # orbax StandardSave rejects numpy scalar types; 0-d arrays
        # round-trip fine (restored as shape-() arrays)
        return np.asarray(obj)
    if isinstance(obj, (dict,)):
        return {k: _to_raw_tree(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_raw_tree(v) for v in obj]  # orbax prefers lists
    return obj


def _wrap_tree(obj):
    if isinstance(obj, (jax.Array, np.ndarray)):
        return Tensor(obj)
    if isinstance(obj, dict):
        return {k: _wrap_tree(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_wrap_tree(v) for v in obj]
    return obj


def _target_from_shardings(metadata, shardings):
    """Abstract restore target: checkpoint metadata supplies shape/dtype,
    the shardings tree supplies placement (the converter.py analog: each
    leaf restores straight into the NEW strategy's sharding). The
    shardings tree must cover the full checkpoint tree."""

    metadata = getattr(metadata, "item_metadata", metadata)  # StepMetadata

    def walk(sh, md_node):
        if isinstance(sh, dict):
            return {k: walk(v, md_node[k]) for k, v in sh.items()}
        if isinstance(sh, (list, tuple)):
            return [walk(v, md_node[i]) for i, v in enumerate(sh)]
        return jax.ShapeDtypeStruct(tuple(md_node.shape), md_node.dtype,
                                    sharding=sh)

    return walk(shardings, metadata)


class CheckpointManager:
    """Epoch/step-granular async sharded checkpoints with retention.

    Usage:
        mgr = CheckpointManager(dir, max_to_keep=3, async_save=True)
        mgr.save(step, {"model": model.state_dict(),
                        "opt": opt.state_dict()})
        ...
        state = mgr.restore()                 # latest
        state = mgr.restore(step=7)
        mgr.wait()                            # block on in-flight saves
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = True,
                 save_interval_steps: int = 1):
        import orbax.checkpoint as ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._ocp = ocp
        self._async = bool(async_save)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save)
        self._mgr = ocp.CheckpointManager(self.directory, options=options)
        # commit markers for async saves flush in wait(), when the data
        # they vouch for has actually hit disk
        self._pending_markers: Dict[int, Dict[str, Any]] = {}
        self._unregister_emergency: Optional[Callable[[], None]] = None
        self.last_restored_step: Optional[int] = None

    def save(self, step: int, state: Dict[str, Any],
             force: bool = False) -> bool:
        """Queues (async) or writes a checkpoint of the (possibly
        sharded) state tree. Returns False if skipped by
        save_interval_steps (``force=True`` bypasses the interval — the
        emergency-save path)."""
        raw = _to_raw_tree(state)
        meta = _leaf_metadata(raw)
        args = self._ocp.args.StandardSave(raw)
        try:
            saved = self._mgr.save(step, args=args, force=force)
        except self._ocp.checkpoint_manager.StepAlreadyExistsError:
            if not force:
                raise
            # forced (emergency) save of a step the periodic path just
            # committed: the state is already on disk — that IS success,
            # not a failure to swallow (make sure the marker exists too)
            self._write_marker(int(step), meta)
            return True
        if saved:
            if self._async:
                self._pending_markers[int(step)] = meta
            else:
                self._write_marker(int(step), meta)
        return saved

    # ------------------------------------------------- commit markers
    def _marker_path(self, step: int) -> str:
        return os.path.join(self.directory, str(step), COMMIT_MARKER)

    def _write_marker(self, step: int, meta: Dict[str, Any]) -> None:
        if jax.process_index() != 0:
            return
        step_dir = os.path.join(self.directory, str(step))
        if not os.path.isdir(step_dir):  # e.g. already garbage-collected
            return
        tmp = self._marker_path(step) + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"step": int(step), "leaves": meta}, f)
            os.replace(tmp, self._marker_path(step))
            # black-box breadcrumb: a post-mortem dump shows which step
            # last committed, next to the preemption/watchdog events
            flight_recorder.record("checkpoint.commit", step=int(step))
        except OSError as e:
            monitor.record_swallowed("checkpoint.commit_marker", e)

    def validate(self, step: int) -> bool:
        """Structural pre-check of a committed step: the commit marker's
        leaf shapes/dtypes must match orbax's on-disk metadata. A step
        with NO marker passes (legacy checkpoints predate markers) — a
        present-but-unreadable or mismatched marker fails."""
        marker = self._marker_path(step)
        if not os.path.exists(marker):
            return True
        try:
            with open(marker) as f:
                recorded = json.load(f)["leaves"]
        except (OSError, ValueError, KeyError):
            return False
        try:
            md = self._mgr.item_metadata(step)
        except Exception:
            md = None
        if md is None:
            # metadata unavailable (fresh manager without a handler
            # registry): inconclusive, let the restore attempt decide
            return True
        on_disk = _flatten_tree(md.tree)  # orbax TreeMetadata
        if not on_disk:
            return True  # metadata empty/unreconstructable: inconclusive
        for path, leaf in recorded.items():
            if leaf["shape"] is None:
                continue  # non-array leaf: no orbax shape contract
            got = on_disk.get(path)
            if got is None or list(getattr(got, "shape", ())) != \
                    leaf["shape"]:
                return False
            got_dtype = getattr(got, "dtype", None)
            if got_dtype is not None and \
                    str(np.dtype(got_dtype)) != leaf["dtype"]:
                return False
        return True

    # ------------------------------------------------------- restore
    def restore(self, step: Optional[int] = None, shardings=None,
                fallback: Optional[bool] = None):
        """Restore a state tree; `shardings` (same tree structure, leaves
        = NamedSharding) reshards on the fly — the cross-strategy
        converter. Returns Tensors.

        Fallback: when restoring the latest step (``step=None``, or any
        step with ``fallback=True``), a truncated/uncommitted candidate
        is skipped and the next older step is tried, each skip reported
        via ``core.monitor`` (``resilience.ckpt.fallback``). An explicit
        ``step`` with ``fallback=False`` (the default there) raises
        ``CheckpointCorruption`` instead."""
        self.wait()
        steps = self.all_steps()
        if fallback is None:
            fallback = step is None
        if step is None:
            candidates = list(reversed(steps))
        elif fallback:
            candidates = [s for s in reversed(steps) if s <= step]
        else:
            candidates = [step]
        if not candidates:
            return None

        skipped: List[int] = []
        last_err: Optional[BaseException] = None
        for s in candidates:
            if not self.validate(s):
                err = CheckpointCorruption(
                    f"checkpoint step {s} in {self.directory}: commit "
                    f"marker mismatch")
                if not fallback:
                    # explicit step, no fallback: the caller gets the
                    # specific diagnosis, and no fallback metric fires
                    raise err
                monitor.record_ckpt_fallback(s)
                monitor.record_swallowed("checkpoint.restore", err)
                skipped.append(s)
                continue
            try:
                tree = self._restore_step(s, shardings)
            except Exception as e:  # truncated/corrupt payload
                if not fallback:
                    raise CheckpointCorruption(
                        f"checkpoint step {s} in {self.directory} failed "
                        f"to restore: {e}") from e
                monitor.record_ckpt_fallback(s)
                monitor.record_swallowed("checkpoint.restore", e)
                skipped.append(s)
                last_err = e
                continue
            if skipped:
                import sys
                sys.stderr.write(
                    f"CheckpointManager: skipped corrupt/uncommitted "
                    f"step(s) {skipped}, restored step {s} from "
                    f"{self.directory}\n")
            self.last_restored_step = s
            return _wrap_tree(tree)
        raise CheckpointCorruption(
            f"no restorable checkpoint in {self.directory}: tried "
            f"{candidates}, skipped {skipped}"
            + (f"; last error: {last_err}" if last_err else ""))

    def _restore_step(self, step: int, shardings=None):
        if shardings is not None:
            md = self._mgr.item_metadata(step)
            target = _target_from_shardings(md, shardings)
            args = self._ocp.args.StandardRestore(target)
        else:
            args = self._ocp.args.StandardRestore()
        return self._mgr.restore(step, args=args)

    # ------------------------------------------------------ lifecycle
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self):
        return sorted(self._mgr.all_steps())

    def wait(self, timeout: Optional[float] = None):
        """Block on in-flight async saves, then publish their commit
        markers. ``timeout`` (or PADDLE_WATCHDOG_CKPT_S) arms the hang
        watchdog around the orbax wait."""
        from . import resilience
        if timeout is None:
            timeout = resilience.env_timeout("PADDLE_WATCHDOG_CKPT_S")
        resilience.guarded_call(self._mgr.wait_until_finished,
                                label="checkpoint.wait", timeout=timeout)
        if self._pending_markers:
            done = set(self._mgr.all_steps())
            for s, meta in list(self._pending_markers.items()):
                if s in done:
                    self._write_marker(s, meta)
                del self._pending_markers[s]

    def save_on_preemption(self, state_fn: Callable[[], Dict[str, Any]]
                           ) -> Callable[[], None]:
        """Register this manager for the resilience layer's emergency
        save: on preemption, ``state_fn()`` is checkpointed synchronously
        at the preempted step (interval bypassed). Returns an unregister
        callable; ``close()`` also unregisters."""
        from . import resilience

        def _emergency(step: int) -> None:
            self.save(step, state_fn(), force=True)
            self.wait()

        if self._unregister_emergency is not None:
            self._unregister_emergency()
        self._unregister_emergency = resilience.register_emergency(
            _emergency)
        return self._unregister_emergency

    def close(self):
        if self._unregister_emergency is not None:
            self._unregister_emergency()
            self._unregister_emergency = None
        self.wait()
        self._mgr.close()


# ------------------------------------------------------- one-shot helpers

def save_sharded(state: Dict[str, Any], path: str,
                 async_save: bool = False):
    """One-shot sharded save (paddle.save analog for distributed state:
    every host writes its addressable shards; call from ALL hosts).
    With async_save=True, returns the checkpointer — call its
    wait_until_finished() before exiting."""
    import orbax.checkpoint as ocp
    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, _to_raw_tree(state), force=True)
    if not async_save:
        ckptr.wait_until_finished()
    return ckptr


def load_sharded(path: str, shardings=None):
    """One-shot restore; `shardings` reshards onto a new strategy
    (must mirror the full checkpoint tree when given)."""
    import orbax.checkpoint as ocp
    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    if shardings is not None:
        target = _target_from_shardings(ckptr.metadata(path), shardings)
        tree = ckptr.restore(path, target)
    else:
        tree = ckptr.restore(path)
    return _wrap_tree(tree)


def shardings_for_model(model, mesh=None, strategy=None):
    """NamedSharding tree matching a model's state_dict under the active
    mesh + ZeRO strategy — feed to restore(shardings=...) to convert a
    checkpoint to this strategy (≈ auto_parallel/converter.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from . import topology
    from .parallel.sharding import ShardingStrategy
    mesh = mesh or topology.get_mesh()
    if mesh is None:
        return None
    strategy = strategy or ShardingStrategy(stage=0)
    out = {}
    params = dict(model.named_parameters())
    for name, t in model.state_dict().items():
        base = getattr(t, "spec", None)
        if name in params:
            spec = strategy.param_spec(tuple(t.shape), mesh,
                                       base if base is not None else P())
        else:
            spec = base if base is not None else P()
        out[name] = NamedSharding(mesh, spec)
    return out

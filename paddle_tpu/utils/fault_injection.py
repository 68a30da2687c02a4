"""Deterministic in-process fault injection — the chaos-test harness.

Everything here is process-local and deterministic: faults fire on exact
call counts or exact byte offsets, never on wall-clock races, so a chaos
test that passes once passes always, and no real TPU (or even a second
process) is needed.

Injectable faults:

- ``KillAfter(n)``              — deliver a signal to this process on the
                                  n-th ``step()`` call (preemption).
- ``truncate_checkpoint(...)``  — truncate the largest payload file of a
                                  checkpoint step (torn write).
- ``remove_commit_marker(...)`` — delete a step's commit marker
                                  (writer died between data and commit).
- ``StoreFaults(...)``          — delay or drop TCPStore responses for
                                  chosen ops/keys (network stall, hang).
- ``poison_batch(...)``         — NaN-fill the float leaves of a batch
                                  (numeric anomaly; trace-compatible:
                                  the poison is in the data, so in-jit
                                  non-finite guards see it).
- ``NaNLoss(loss_fn, at_calls)``— eager loss wrapper returning NaN on
                                  chosen calls (host-side loops only;
                                  under jit the call count is a
                                  trace-time constant — use
                                  poison_batch there).
- ``kill_worker(...)``          — SIGKILL one of a DataLoader's worker
                                  processes (crashed/OOM-killed worker;
                                  drives the supervised respawn path).
- ``truncate_executable(...)``  — truncate a serialized-executable
                                  entry of a ``jit.compile_cache``
                                  store (torn write during relaunch).
- ``corrupt_executable(...)``   — flip payload bytes of an entry (bit
                                  rot; the checksum must catch it and
                                  the load must fall back to compile).
- ``suspend_worker(...)``       — SIGSTOP a worker (wedged worker; the
                                  per-fetch deadline must fire).
- ``FlakySamples(ds, ...)``     — dataset wrapper raising / returning
                                  NaN samples at exact indices (drives
                                  error attribution and quarantine).
- ``wedge_replica(engine)``     — suspend a ServingEngine's scheduler
                                  loop until released (wedged replica:
                                  alive, answers health(), makes zero
                                  progress — the serving-side twin of
                                  ``suspend_worker``).
- ``fail_admission(engine, n)`` — inject ``n`` consecutive admission
                                  failures into a ServingEngine
                                  (pre-prefill by default, so the
                                  failed request is re-routable: drives
                                  the router's circuit breaker; or at
                                  the admit program, or at the deferred
                                  wait for the prefill's token).
"""
from __future__ import annotations

import os
import signal
import time
from typing import Iterable, Optional, Sequence

__all__ = [
    "FlakySamples",
    "KillAfter",
    "NaNLoss",
    "StoreFaults",
    "checkpoint_data_files",
    "corrupt_executable",
    "dataloader_workers",
    "executable_entries",
    "fail_admission",
    "kill_worker",
    "poison_batch",
    "remove_commit_marker",
    "resume_worker",
    "suspend_worker",
    "truncate_checkpoint",
    "truncate_executable",
    "wedge_replica",
]


class KillAfter:
    """Preemption injector: ``step()`` each training step; the ``n``-th
    call sends ``sig`` (default SIGTERM) to this very process — exactly
    what a TPU maintenance event looks like from inside the job."""

    def __init__(self, n: int, sig: int = signal.SIGTERM):
        if n < 1:
            raise ValueError("KillAfter fires on the n-th step, n >= 1")
        self.n = int(n)
        self.sig = sig
        self.calls = 0
        self.fired = False

    def step(self) -> bool:
        """Returns True on the call that delivered the signal."""
        self.calls += 1
        if self.calls == self.n and not self.fired:
            self.fired = True
            os.kill(os.getpid(), self.sig)
            return True
        return False


def _step_dirs(directory: str):
    out = []
    for name in os.listdir(directory):
        if name.isdigit() and os.path.isdir(os.path.join(directory, name)):
            out.append(int(name))
    return sorted(out)


def checkpoint_data_files(directory: str,
                          step: Optional[int] = None) -> list:
    """The payload files of a checkpoint step (the latest when ``step``
    is None): every file under the step dir except metadata/marker
    files (leading underscore). Sorted — deterministic for a given
    on-disk state."""
    steps = _step_dirs(directory)
    if not steps:
        raise FileNotFoundError(f"no checkpoint steps under {directory}")
    step = steps[-1] if step is None else int(step)
    root = os.path.join(directory, str(step))
    out = []
    for dirpath, _, files in os.walk(root):
        for f in files:
            # metadata/marker files are covered by remove_commit_marker;
            # a torn write hits the bulk payload
            if not f.startswith("_"):
                out.append(os.path.join(dirpath, f))
    if not out:
        raise FileNotFoundError(f"no data files under {root}")
    return sorted(out)


def truncate_checkpoint(directory: str, step: Optional[int] = None,
                        keep_bytes: int = 0) -> list:
    """Truncate every payload file of a checkpoint step (the latest
    when ``step`` is None) to ``keep_bytes`` — a torn write from a
    preempted saver. All payload files are hit because the storage
    format keeps redundant copies of small trees (OCDBT manifests plus
    per-process blobs): corrupting only one blob may leave the step
    restorable, which would make chaos tests pass or fail on which
    randomly-named file happened to be chosen. Metadata/marker files
    survive, so the step still LOOKS committed — exactly the case the
    restore fallback must catch. Returns the truncated paths."""
    paths = checkpoint_data_files(directory, step)
    for path in paths:
        with open(path, "r+b") as f:
            f.truncate(int(keep_bytes))
    return paths


def remove_commit_marker(directory: str, step: Optional[int] = None) -> str:
    """Delete a step's ``_PADDLE_COMMIT`` marker — the writer died after
    the data landed but before the commit. Returns the removed path."""
    from ..distributed.checkpoint import COMMIT_MARKER
    steps = _step_dirs(directory)
    if not steps:
        raise FileNotFoundError(f"no checkpoint steps under {directory}")
    step = steps[-1] if step is None else int(step)
    path = os.path.join(directory, str(step), COMMIT_MARKER)
    os.remove(path)
    return path


class StoreFaults:
    """Delay or drop TCPStore server responses, deterministically.

    ::

        with StoreFaults(delay=5.0, ops=("get",), count=1):
            store.get("key")          # this one reply stalls 5s

        with StoreFaults(drop=True, ops=("set",), key_prefix="__barrier"):
            ...                       # barrier sets are never answered

    ``count`` bounds how many matching requests fault (None = all while
    installed). Matching is by op name and optional key prefix; the
    fault applies server-side, so every client of the in-process master
    sees it — the chaos-test stand-in for a stalled or partitioned host.
    """

    def __init__(self, delay: float = 0.0, drop: bool = False,
                 ops: Sequence[str] = ("get",),
                 key_prefix: Optional[str] = None,
                 count: Optional[int] = None):
        self.delay = float(delay)
        self.drop = bool(drop)
        self.ops = tuple(ops)
        self.key_prefix = key_prefix
        self.count = count
        self.triggered = 0

    def _matches(self, op: str, args) -> bool:
        if op not in self.ops:
            return False
        if self.key_prefix is not None:
            key = args[0] if args else ""
            if not str(key).startswith(self.key_prefix):
                return False
        return True

    def __call__(self, op: str, args):
        if self.count is not None and self.triggered >= self.count:
            return None
        if not self._matches(op, args):
            return None
        self.triggered += 1
        if self.delay > 0:
            time.sleep(self.delay)
        return "drop" if self.drop else None

    def __enter__(self) -> "StoreFaults":
        from ..distributed import store
        store.set_fault_hook(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        from ..distributed import store
        store.set_fault_hook(None)
        return False


def poison_batch(batch):
    """NaN-fill every float leaf of a (possibly nested) batch — the
    deterministic numeric-anomaly injection. Integer/bool leaves pass
    through (labels stay valid; the NaN reaches the loss through the
    activations)."""
    import numpy as np

    from ..core.tensor import Tensor

    def poison(x):
        if isinstance(x, Tensor):
            return Tensor(poison(x._data))
        arr = np.asarray(x)
        if np.issubdtype(arr.dtype, np.floating):
            return np.full_like(arr, np.nan)
        return x

    def walk(node):
        if isinstance(node, Tensor):
            return poison(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return poison(node)

    return walk(batch)


# -------------------------------------------- executable-store faults

def executable_entries(store_or_root) -> list:
    """The serialized-executable entries of a ``jit.compile_cache``
    store (an :class:`~paddle_tpu.jit.compile_cache.ExecutableStore`
    or its root dir), sorted — deterministic handle for the
    corruptions below."""
    root = getattr(store_or_root, "root", store_or_root)
    from ..jit.compile_cache import ENTRY_SUFFIX
    try:
        names = os.listdir(root)
    except OSError:
        raise FileNotFoundError(f"no executable store at {root}")
    out = sorted(os.path.join(root, n) for n in names
                 if n.endswith(ENTRY_SUFFIX))
    if not out:
        raise FileNotFoundError(f"no executable entries under {root}")
    return out


def truncate_executable(store_or_root, index: int = 0,
                        keep_bytes: int = 0) -> str:
    """Truncate one store entry to ``keep_bytes`` — a torn write from a
    process killed mid-relaunch. The next load of that program must
    fall back to a fresh compile (``jit.compile_cache.misses{cause=
    corrupt}``) and rewrite a good entry. Returns the truncated
    path."""
    path = executable_entries(store_or_root)[index]
    with open(path, "r+b") as f:
        f.truncate(int(keep_bytes))
    return path


def corrupt_executable(store_or_root, index: int = 0,
                       offset: int = -64, n: int = 8) -> str:
    """XOR-flip ``n`` bytes of one store entry at ``offset`` (negative:
    from the end — the payload tail, past the checksum header) — bit
    rot the entry's sha256 must catch. Returns the corrupted path."""
    path = executable_entries(store_or_root)[index]
    with open(path, "r+b") as f:
        f.seek(offset, os.SEEK_END if offset < 0 else os.SEEK_SET)
        pos = f.tell()
        data = f.read(int(n))
        f.seek(pos)
        f.write(bytes(b ^ 0xFF for b in data))
    return path


# ------------------------------------------------- dataloader faults

def dataloader_workers(loader_or_iter) -> list:
    """The live worker processes of a DataLoader (its active iterator)
    or of a ``_PrefetchIterator`` directly. Deterministic handle for
    the kill/suspend injections below."""
    it = loader_or_iter
    active = getattr(it, "_active_iter", None)
    if callable(active):  # a DataLoader: reach through to the iterator
        it = active()
    if it is None:
        raise RuntimeError("DataLoader has no active iterator")
    workers = [w for w in getattr(it, "_workers", []) if w is not None]
    if not workers:
        raise RuntimeError("no worker processes (num_workers=0?)")
    return workers


def kill_worker(loader_or_iter, worker_id: int = 0,
                sig: int = signal.SIGKILL) -> int:
    """Deliver ``sig`` (default SIGKILL — a crash/OOM-kill) to one
    DataLoader worker. The supervisor must respawn it and re-dispatch
    its in-flight batches with no change to the batch stream. Returns
    the killed pid."""
    p = dataloader_workers(loader_or_iter)[worker_id]
    os.kill(p.pid, sig)
    return p.pid


def suspend_worker(loader_or_iter, worker_id: int = 0) -> int:
    """SIGSTOP a worker — the deterministic 'wedged worker' fault: the
    process stays alive (liveness checks pass) but never produces, so
    the per-fetch deadline must surface a WatchdogTimeout. Returns the
    pid (pass to ``resume_worker`` for cleanup, or let the iterator's
    teardown SIGKILL it)."""
    p = dataloader_workers(loader_or_iter)[worker_id]
    os.kill(p.pid, signal.SIGSTOP)
    return p.pid


def resume_worker(pid: int) -> None:
    """SIGCONT a worker suspended by ``suspend_worker``."""
    try:
        os.kill(pid, signal.SIGCONT)
    except ProcessLookupError:
        pass  # teardown already reaped it


class FlakySamples:
    """Map-style dataset wrapper that fails on exact sample indices:
    ``raise_at`` indices raise ValueError, ``nan_at`` indices return
    the sample with every float leaf NaN-filled. Drives the
    DataLoader's error-attribution and quarantine paths without
    touching the wrapped dataset."""

    def __init__(self, dataset, raise_at: Iterable[int] = (),
                 nan_at: Iterable[int] = ()):
        self.dataset = dataset
        self.raise_at = frozenset(int(i) for i in raise_at)
        self.nan_at = frozenset(int(i) for i in nan_at)

    def __len__(self):
        return len(self.dataset)

    def __getitem__(self, idx):
        if int(idx) in self.raise_at:
            raise ValueError(f"FlakySamples: injected failure at "
                             f"sample {int(idx)}")
        sample = self.dataset[idx]
        if int(idx) in self.nan_at:
            return poison_batch(sample)
        return sample


# -------------------------------------------- serving replica faults

class wedge_replica:
    """Suspend a ServingEngine's scheduler until released — the
    deterministic 'wedged replica' fault (the serving-side twin of
    ``suspend_worker``): the engine stays alive and keeps answering
    ``submit()``/``health()``, but ``step()`` and the inline
    ``result()`` pump make zero progress, so its queue only grows. A
    multi-replica router must observe the mounting backpressure
    (``queue_full`` health reasons, falling score) and steer traffic to
    survivors. Context manager, or ``release()`` explicitly::

        with wedge_replica(engine):
            ...                      # engine frozen, deterministically
        # scheduler restored; queued work resumes
    """

    def __init__(self, engine):
        self.engine = engine
        self._saved = None

    def wedge(self) -> "wedge_replica":
        if self._saved is None:
            self._saved = (self.engine.step, self.engine._try_pump)
            self.engine.step = lambda: None
            self.engine._try_pump = lambda: False
        return self

    def release(self):
        if self._saved is not None:
            self.engine.step, self.engine._try_pump = self._saved
            self._saved = None

    def __enter__(self) -> "wedge_replica":
        return self.wedge()

    def __exit__(self, exc_type, exc, tb):
        self.release()
        return False


class fail_admission:
    """Inject ``n`` consecutive admission failures into a
    ServingEngine, at one of three points of an admission (``at``):

    - ``"fetch"`` (default): the next ``n`` requests popped for
      admission raise at the prefill-executable fetch — BEFORE any
      prefill dispatch or KV write, so the failed admission is
      idempotent and a router may re-route the request to another
      replica;
    - ``"admit"``: the prefill is dispatched, then the fetch of the
      admit program raises — the request has left the queue and holds
      committed pages, but reached no slot;
    - ``"wait"``: the prefill and the admit program run, and the
      DEFERRED wait for the prefill's token raises, as a prefill that
      failed on the device would — the request already sits in its slot
      and the iteration's decode step is queued behind it.

    The engine's own handling cancels each doomed handle with an
    ``admission error: ...`` detail (its Future never hangs), returns
    its pages and frees its slot; ``triggered`` counts faults actually
    fired. Composes with ``KillAfter``/``StoreFaults``::

        with fail_admission(engine, n=3):
            ...   # the next 3 admissions on this engine fail
    """

    def __init__(self, engine, n: int = 1, at: str = "fetch"):
        if n < 1:
            raise ValueError("fail_admission fires on n >= 1 admissions")
        if at not in ("fetch", "admit", "wait"):
            raise ValueError(f"fail_admission at {at!r}: one of 'fetch', "
                             "'admit', 'wait'")
        self.engine = engine
        self.n = int(n)
        self.at = at
        self.triggered = 0
        self._orig = None

    def _fire(self):
        self.triggered += 1
        raise RuntimeError(
            f"fail_admission: injected admission failure "
            f"{self.triggered}/{self.n}")

    def __enter__(self) -> "fail_admission":
        # the engine method each point goes through, and when it fires
        name, fires = {
            "fetch": ("_exe_prefill", lambda bucket: True),
            "admit": ("_compiled", lambda key: key == ("admit",)),
            "wait": ("_sync", lambda site, *_: site == "prefill"),
        }[self.at]
        orig = getattr(self.engine, name)

        def flaky(*args):
            if self.triggered < self.n and fires(*args):
                if self.at != "wait":
                    self._fire()
                # raise inside the read, under its span, where a device
                # error surfaces
                args = (args[0], self._fire) + args[2:]
            return orig(*args)

        self._orig = (name, orig)
        setattr(self.engine, name, flaky)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._orig is not None:
            setattr(self.engine, *self._orig)
            self._orig = None
        return False


class NaNLoss:
    """Eager-path loss wrapper: returns NaN on the given (1-based) call
    numbers, delegates otherwise. Host-side loops only — under jit the
    call counter is a trace-time constant (use ``poison_batch``)."""

    def __init__(self, loss_fn, at_calls: Iterable[int]):
        self.loss_fn = loss_fn
        self.at_calls = frozenset(int(i) for i in at_calls)
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        out = self.loss_fn(*args, **kwargs)
        if self.calls in self.at_calls:
            import numpy as np

            from ..core.tensor import Tensor
            return Tensor(np.float32(np.nan)) if isinstance(out, Tensor) \
                else float("nan")
        return out

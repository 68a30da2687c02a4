"""Fault-tolerance layer: preemption-safe checkpoints, hang watchdog,
anomaly guard.

Reference analogs: Fleet's ElasticManager treats worker death as a
first-class event (manager.py restarts on exit codes 101/102) and
fluid/incubate/checkpoint/auto_checkpoint.py gives transparent resume —
but both assume the happy path inside one run. On real TPU pods
maintenance events preempt hosts mid-step, collectives hang when a slice
re-forms, and a preempted writer leaves truncated checkpoints. This
module is the glue that turns those into survivable events:

- ``GracefulShutdown``: SIGTERM/SIGINT → cross-host "preempted" flag in
  the TCPStore → synchronous emergency checkpoint of registered state →
  ``sys.exit(ELASTIC_EXIT_CODE)`` so the launcher relaunches and the
  training loop resumes from the emergency step.
- ``Watchdog``: armed around collectives, TCPStore ops and checkpoint
  waits; past the deadline it dumps every thread's stack to stderr,
  bumps the ``resilience.watchdog.timeouts`` counter and raises
  ``WatchdogTimeout`` instead of hanging the pod forever.
- ``AnomalyGuard``: non-finite loss → skip the batch; N consecutive
  anomalies → restore from the last good checkpoint.

The checkpoint-integrity half (commit markers, corruption fallback)
lives in ``distributed.checkpoint``; ``utils.fault_injection`` is the
chaos-test harness that drives all of it deterministically in-process.
"""
from __future__ import annotations

import ctypes
import os
import signal
import sys
import threading
import time
from typing import Callable, List, Optional, Tuple

from ..core import flight_recorder, monitor
from .elastic import ELASTIC_EXIT_CODE

__all__ = [
    "AnomalyGuard",
    "GracefulShutdown",
    "Watchdog",
    "WatchdogTimeout",
    "active",
    "dump_stacks",
    "poll",
    "preempted",
    "register_emergency",
    "watchdog",
]

PREEMPT_KEY = "__resilience/preempted"


class WatchdogTimeout(RuntimeError):
    """An armed watchdog expired: the guarded operation overran its
    deadline (thread stacks were dumped to stderr when it fired)."""


# --------------------------------------------------------------- watchdog

def dump_stacks(label: str, timeout: float) -> None:
    """Dump every thread's stack to stderr in the watchdog's format —
    for deadline guards that detect the overrun themselves (the
    DataLoader's per-fetch supervisor) and want the same diagnostics a
    fired ``Watchdog`` produces."""
    _dump_all_stacks(label, timeout)


def _dump_all_stacks(label: str, timeout: float) -> None:
    sys.stderr.write(_all_stacks_text(label, timeout))
    sys.stderr.flush()


def _all_stacks_text(label: str, timeout: float) -> str:
    """The watchdog's dump as text: every other thread's stack in the
    recorder's ``file:line function`` form (what its stall watcher
    samples), outermost frame first."""
    lines = [f"\n=== Watchdog '{label}' expired after {timeout:.1f}s — "
             f"dumping {threading.active_count()} thread stacks ==="]
    for thread, frames in flight_recorder.thread_stacks().items():
        lines.append(f"--- thread {thread} ---")
        lines.extend("  " + f for f in reversed(frames))
    lines.append("=== end watchdog dump ===\n")
    return "\n".join(lines)


_tls = threading.local()


def _armed_watchdog() -> Optional["Watchdog"]:
    """The innermost watchdog armed on the CURRENT thread (blocking ops
    like TCPStore calls register their cancellers against it)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class Watchdog:
    """Deadline monitor for operations that can hang forever.

    Context-manager form — arms a timer around the guarded region::

        with Watchdog(timeout=60, label="allreduce"):
            dist.all_reduce(x)

    On expiry the monitor thread dumps all thread stacks, bumps the
    ``resilience.watchdog.timeouts`` counter, runs any registered
    cancellers (e.g. force-closing a TCPStore socket so its blocked recv
    aborts) and injects ``WatchdogTimeout`` into the armed thread. Pure
    C-level blocks that ignore async exceptions are un-hung only by a
    canceller; ``Watchdog.run`` is the guaranteed form for those::

        Watchdog.run(mgr.wait, timeout=120, label="ckpt.wait")

    runs the callable on a worker thread and abandons it on timeout (the
    daemon worker keeps blocking, the caller gets WatchdogTimeout).
    """

    def __init__(self, timeout: float, label: str = "op",
                 dump_stacks: bool = True):
        self.timeout = float(timeout)
        self.label = label
        self.dump_stacks = dump_stacks
        self.expired = False
        self._timer: Optional[threading.Timer] = None
        self._owner: Optional[int] = None
        self._cancellers: List[Callable[[], None]] = []
        self._lock = threading.Lock()
        self._closed = False  # __exit__ ran: _fire must stand down

    # ------------------------------------------------------------ cancellers
    def add_canceller(self, fn: Callable[[], None]) -> None:
        """Register a callback the expiry path runs to abort the guarded
        op at its source (close a socket, kill a subprocess, ...)."""
        with self._lock:
            self._cancellers.append(fn)

    def remove_canceller(self, fn: Callable[[], None]) -> None:
        with self._lock:
            try:
                self._cancellers.remove(fn)
            except ValueError:
                pass

    # ------------------------------------------------------------- lifecycle
    def _fire(self) -> None:
        with self._lock:
            if self._closed:  # lost the race against __exit__: no-op
                return
            self.expired = True
        if self.dump_stacks:
            _dump_all_stacks(self.label, self.timeout)
        monitor.record_watchdog_timeout(self.label)
        # the black box: record the expiry and dump the ring — a hung
        # process about to be force-killed must leave behind what it
        # was doing (the stalled request's spans, the last compiles)
        flight_recorder.record("watchdog.timeout", label=self.label,
                               timeout_s=self.timeout)
        flight_recorder.auto_dump("watchdog")
        # abort actions run under the lock and re-check _closed, so a
        # region that exited between the dump and here is never hit: no
        # closing a socket some LATER op now owns, no async exception
        # left pending to detonate at an arbitrary later bytecode
        with self._lock:
            if self._closed:
                return
            if self._cancellers:
                # a canceller aborts the guarded op at its source
                # (closed socket -> ConnectionError); __exit__ converts
                # that abort to WatchdogTimeout. Never ALSO inject an
                # async exception: the op unwinds immediately, and a
                # still-pending injection would land later, anywhere.
                for fn in list(self._cancellers):
                    try:
                        fn()
                    except Exception as e:
                        monitor.record_swallowed(
                            f"watchdog.cancel:{self.label}", e)
            elif self._owner is not None:
                # no canceller: best-effort injection, delivered at the
                # thread's next bytecode boundary — un-hangs pure-Python
                # waits; C-level blocks need a canceller or Watchdog.run
                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_ulong(self._owner),
                    ctypes.py_object(WatchdogTimeout))

    def __enter__(self) -> "Watchdog":
        self.expired = False
        self._closed = False
        self._owner = threading.get_ident()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        self._timer = threading.Timer(self.timeout, self._fire)
        self._timer.daemon = True
        self._timer.name = f"watchdog:{self.label}"
        self._timer.start()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._timer is not None:
            self._timer.cancel()
        with self._lock:
            # close under the same lock _fire acts under: either its
            # abort actions already happened (retracted just below) or
            # its _closed re-check makes them a no-op — never a stray
            # injection after this region is gone
            self._closed = True
            if self.expired and self._owner is not None:
                # retract a still-pending async exception so it cannot
                # surface at an arbitrary later point
                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_ulong(self._owner), None)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        if self.expired:
            msg = (f"watchdog '{self.label}' expired after "
                   f"{self.timeout:.1f}s")
            if exc is not None and not isinstance(exc, WatchdogTimeout):
                # the canceller aborted the op with its own error
                # (ConnectionError from a closed socket, ...): surface
                # the deadline, keep the abort as the cause
                raise WatchdogTimeout(msg) from exc
            if exc is None:
                raise WatchdogTimeout(msg)
        return False

    # -------------------------------------------------------- threaded form
    @staticmethod
    def run(fn: Callable, *args, timeout: float, label: str = "op",
            dump_stacks: bool = True, **kwargs):
        """Run ``fn`` with a hard deadline: the call happens on a daemon
        worker thread; if it overruns, the worker is abandoned and
        ``WatchdogTimeout`` raises in the caller. Use for blocking calls
        that cannot be cancelled (collective dispatch, orbax waits)."""
        result: list = []
        error: list = []

        def target():
            try:
                result.append(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 — relayed below
                error.append(e)

        worker = threading.Thread(target=target, daemon=True,
                                  name=f"watchdog.run:{label}")
        worker.start()
        worker.join(timeout)
        if worker.is_alive():
            if dump_stacks:
                _dump_all_stacks(label, timeout)
            monitor.record_watchdog_timeout(label)
            flight_recorder.record("watchdog.timeout", label=label,
                                   timeout_s=float(timeout))
            flight_recorder.auto_dump("watchdog")
            raise WatchdogTimeout(
                f"watchdog '{label}' expired after {timeout:.1f}s "
                f"(worker thread abandoned)")
        if error:
            raise error[0]
        return result[0]


def watchdog(timeout: float, label: str = "op",
             dump_stacks: bool = True) -> Watchdog:
    """`with watchdog(30, "store.get"): ...` — sugar over Watchdog."""
    return Watchdog(timeout, label=label, dump_stacks=dump_stacks)


def env_timeout(var: str) -> Optional[float]:
    """Parse a watchdog deadline from the environment; None/0 = off."""
    raw = os.environ.get(var, "").strip()
    if not raw:
        return None
    try:
        t = float(raw)
    except ValueError:
        return None
    return t if t > 0 else None


# ---------------------------------------------------- emergency checkpoint

# (save_fn(step) -> None) registered process-wide; GracefulShutdown runs
# every entry synchronously when a preemption lands. CheckpointManager.
# save_on_preemption and hapi's ModelCheckpoint both register here.
_EMERGENCY: List[Tuple[int, Callable[[int], None]]] = []
_EMERGENCY_LOCK = threading.Lock()
_EMERGENCY_SEQ = 0


def register_emergency(save_fn: Callable[[int], None]) -> Callable[[], None]:
    """Register ``save_fn(step)`` to run on preemption; returns an
    unregister callable."""
    global _EMERGENCY_SEQ
    with _EMERGENCY_LOCK:
        _EMERGENCY_SEQ += 1
        entry = (_EMERGENCY_SEQ, save_fn)
        _EMERGENCY.append(entry)

    def unregister():
        with _EMERGENCY_LOCK:
            try:
                _EMERGENCY.remove(entry)
            except ValueError:
                pass

    return unregister


def _run_emergency_saves(step: int) -> int:
    with _EMERGENCY_LOCK:
        entries = list(_EMERGENCY)
    done = 0
    for _, fn in entries:
        try:
            fn(step)
            done += 1
        except Exception as e:
            # one broken saver must not stop the others from committing
            monitor.record_swallowed("emergency_save", e)
    if done:
        monitor.record_emergency_save(step)
    return done


# ------------------------------------------------------- graceful shutdown

_ACTIVE: List["GracefulShutdown"] = []


class GracefulShutdown:
    """Preemption-safe shutdown context for a training loop.

    ::

        mgr = CheckpointManager(path)
        mgr.save_on_preemption(lambda: {"model": model.state_dict()})
        with GracefulShutdown(store=store) as gs:
            for step, batch in enumerate(loader):
                train_step(batch)
                gs.check(step)   # preempted? -> emergency save + exit 101

    The signal handler only sets a flag (no locks, no sockets: the
    signal may land while this very thread holds the store's client
    lock). ``check(step)`` at the next step boundary does the real work:
    broadcast the preemption through the TCPStore so every host saves
    the same step, run all registered emergency saves synchronously, and
    ``sys.exit(ELASTIC_EXIT_CODE)`` so the launcher's elastic path
    relaunches the job, which resumes from the emergency checkpoint.
    """

    def __init__(self, store=None,
                 signals=(signal.SIGTERM, signal.SIGINT),
                 exit_code: int = ELASTIC_EXIT_CODE,
                 exit_on_save: bool = True,
                 key: str = PREEMPT_KEY,
                 store_poll_interval: float = 5.0,
                 incarnation: Optional[str] = None):
        self.store = store
        self.signals = tuple(signals)
        self.exit_code = exit_code
        self.exit_on_save = exit_on_save
        # the flag/election keys are namespaced by the elastic restart
        # incarnation (launcher-exported PADDLE_RESTART_COUNT): keys a
        # previous incarnation published survive in the launcher's
        # store, and a relaunched job reading its predecessor's flag
        # would emergency-exit on its very first step — a crash loop
        if incarnation is None:
            incarnation = os.environ.get("PADDLE_RESTART_COUNT", "0")
        self.key = f"{key}/{incarnation}"
        # store polling is a real RPC: throttle it off the per-batch hot
        # path (the local signal flag is still checked on every call)
        self.store_poll_interval = float(store_poll_interval)
        self._last_store_poll = float("-inf")
        self._signaled = threading.Event()
        self._via_store = False   # detected via the store broadcast,
        #                           not a local signal (peer, not victim)
        self._prev_handlers = {}
        self._installed = False

    # --------------------------------------------------------------- signals
    def _handler(self, signum, frame):
        # async-signal-safe by construction: set a flag, nothing else
        self._signaled.set()

    def __enter__(self) -> "GracefulShutdown":
        if threading.current_thread() is threading.main_thread():
            for sig in self.signals:
                self._prev_handlers[sig] = signal.signal(sig, self._handler)
            self._installed = True
        else:
            monitor.record_swallowed(
                "graceful_shutdown.install",
                RuntimeError("signal handlers need the main thread; "
                             "relying on store flag polling only"))
        _ACTIVE.append(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._installed:
            for sig, prev in self._prev_handlers.items():
                try:
                    signal.signal(sig, prev)
                except (ValueError, OSError) as e:
                    monitor.record_swallowed("graceful_shutdown.restore", e)
            self._prev_handlers.clear()
            self._installed = False
        try:
            _ACTIVE.remove(self)
        except ValueError:
            pass
        return False

    # ------------------------------------------------------------- preempted
    @property
    def preempted(self) -> bool:
        """True once this host was signaled OR any host published the
        preemption flag to the store. The local flag costs nothing and
        is read every call; the store check is one keys() RPC, rate-
        limited to ``store_poll_interval`` seconds so per-batch polling
        stays off the hot path."""
        if self._signaled.is_set():
            return True
        if self.store is not None:
            now = time.monotonic()
            if now - self._last_store_poll < self.store_poll_interval:
                return False
            self._last_store_poll = now
            try:
                if self.store.keys(self.key):
                    if not self._signaled.is_set():
                        self._via_store = True
                    self._signaled.set()
                    return True
            except (TimeoutError, RuntimeError, OSError) as e:
                monitor.record_swallowed("graceful_shutdown.poll", e)
        return False

    def trigger(self) -> None:
        """Programmatic preemption (tests, cluster-notice pollers)."""
        self._signaled.set()

    # ----------------------------------------------------------------- check
    def check(self, step: int) -> bool:
        """Call at every step boundary. Returns False in the happy path;
        on preemption: broadcast flag → emergency save → exit."""
        if not self.preempted:
            return False
        from ..core import goodput
        t_recover = time.perf_counter()
        monitor.record_preemption()
        # the preemption dump happens BEFORE the emergency saves: if a
        # save wedges, the black box already shows the step the process
        # reached and everything it was doing when the signal landed.
        # source distinguishes the VICTIM (the signal landed here) from
        # peers that detected it through the store broadcast — the
        # merged fleet trace orders the SIGTERM instant before the
        # detections
        flight_recorder.record("resilience.preemption", step=int(step),
                               source="store" if self._via_store
                               else "signal")
        flight_recorder.auto_dump("preemption")
        save_step = int(step)
        if self.store is not None:
            try:
                # atomic election via the store's add counter: exactly
                # one host (the first) publishes ITS step; everyone
                # else blocks briefly for that value and adopts it, so
                # all hosts checkpoint under the same step id even when
                # simultaneously signaled a boundary apart
                if self.store.add(f"{self.key}/elect", 1) == 1:
                    self.store.set(self.key, save_step)
                else:
                    save_step = int(self.store.get(self.key, timeout=10.0))
            except (TimeoutError, RuntimeError, OSError) as e:
                monitor.record_swallowed("graceful_shutdown.broadcast", e)
        _run_emergency_saves(save_step)
        # the whole detection->broadcast->emergency-save window is
        # preemption recovery in the goodput ledger (ambient no-op
        # outside a ledgered loop)
        goodput.charge("preemption_recovery",
                       time.perf_counter() - t_recover)
        if self.exit_on_save:
            sys.exit(self.exit_code)
        return True


def active() -> Optional[GracefulShutdown]:
    """The innermost live GracefulShutdown context, if any."""
    return _ACTIVE[-1] if _ACTIVE else None


def preempted() -> bool:
    gs = active()
    return gs.preempted if gs is not None else False


def poll(step: int) -> bool:
    """Step-boundary hook for loops that did not create the context
    themselves (hapi Model.fit calls this): delegates to the active
    GracefulShutdown's check(), no-op when none is installed."""
    gs = active()
    return gs.check(step) if gs is not None else False


# ----------------------------------------------------------- anomaly guard

class AnomalyGuard:
    """Skip-and-recover policy for non-finite losses.

    ``observe(loss)`` returns True when the loss is usable. A non-finite
    loss is an anomaly: the batch is reported as skipped, and after
    ``max_consecutive`` anomalies in a row ``restore_fn()`` is invoked
    (restore from the last good checkpoint) and the streak resets.
    ``PADDLE_ANOMALY_MAX_CONSECUTIVE`` overrides the threshold."""

    def __init__(self, max_consecutive: int = 3,
                 restore_fn: Optional[Callable[[], None]] = None):
        env = os.environ.get("PADDLE_ANOMALY_MAX_CONSECUTIVE", "").strip()
        try:
            self.max_consecutive = int(env) if env else int(max_consecutive)
        except ValueError:  # env typo must not kill a training job
            monitor.record_swallowed(
                "anomaly_guard.env",
                ValueError(f"PADDLE_ANOMALY_MAX_CONSECUTIVE={env!r}"))
            self.max_consecutive = int(max_consecutive)
        self.restore_fn = restore_fn
        self.consecutive = 0
        self.total = 0
        self.restores = 0

    @staticmethod
    def _finite(loss) -> bool:
        import numpy as np
        try:
            return bool(np.isfinite(np.asarray(
                getattr(loss, "numpy", lambda: loss)(),
                dtype=np.float64)).all())
        except (TypeError, ValueError):
            return True  # non-numeric "loss": not this guard's business

    def observe(self, loss) -> bool:
        if self._finite(loss):
            self.consecutive = 0
            return True
        self.consecutive += 1
        self.total += 1
        monitor.record_anomaly()
        flight_recorder.record("train.anomaly",
                               consecutive=self.consecutive)
        sys.stderr.write(
            f"AnomalyGuard: non-finite loss "
            f"({self.consecutive}/{self.max_consecutive} consecutive); "
            f"skipping batch\n")
        if self.consecutive >= self.max_consecutive:
            self.consecutive = 0
            if self.restore_fn is not None:
                self.restores += 1
                monitor.record_anomaly_restore()
                # dump before rolling back: the events leading into the
                # anomaly streak are the evidence the restore destroys
                flight_recorder.record("train.anomaly_restore",
                                       total=self.total)
                flight_recorder.auto_dump("anomaly_restore")
                sys.stderr.write(
                    "AnomalyGuard: restoring from last good checkpoint\n")
                self.restore_fn()
        return False


# --------------------------------------------------- watchdogged call sugar

def guarded_call(fn: Callable, *args, label: str,
                 timeout: Optional[float] = None, **kwargs):
    """Run ``fn`` under ``Watchdog.run`` when a deadline is configured
    (argument, else the PADDLE_WATCHDOG_<layer> env the caller resolved),
    plainly otherwise. The single chokepoint collectives and checkpoint
    waits route through."""
    if timeout is None or timeout <= 0:
        return fn(*args, **kwargs)
    return Watchdog.run(fn, *args, timeout=timeout, label=label,
                        dump_stacks=True, **kwargs)

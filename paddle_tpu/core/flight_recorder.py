"""Flight recorder: a bounded, thread-safe ring of structured runtime
events — what the process was doing in the seconds before it died.

Reference analog: the reference's platform layer keeps always-on
host-event recorders (HostEventRecorder) that production debugging tools
drain after the fact; the Profiler answers questions only when someone
attached it BEFORE the incident. This module is the black box that is
always on: step boundaries, jit compiles with cause, serving admissions
and evictions, checkpoint commits, collective dispatches, watchdog and
anomaly trips all land in one capacity-bounded ring, and the ring is
auto-dumped (Perfetto-compatible JSON + plaintext tail) when something
dies — Watchdog expiry, AnomalyGuard restore, GracefulShutdown
preemption, an uncaught exception in ``serve_forever``/``fit`` — or on
demand (``dump()``, the telemetry server's ``/flightrecorder``).

Design constraints (the ``core.metrics`` contract):

- sub-microsecond disabled path: every recorder's first action is a
  plain module-global bool check (enforced by
  ``tests/test_overhead_gate.py``);
- enabled cost is one clock read + one locked deque append — cheap
  enough for per-step / per-request / per-collective call sites, and
  the ring bound means a hot loop can never balloon memory;
- the module imports nothing from paddle_tpu at import time (it sits
  below core.monitor; ``monitor`` lazily counts dumps through it).

Spans ride in the same ring as point events: a span is an event whose
kind is ``"span"`` carrying (name, start_ns, end_ns, id, parent id,
trace id, fields). ``span()`` opens one around a block: it becomes the
parent of every span opened inside it on the same thread (so a reader
can compute self time), and it is also a ``jax.profiler.
TraceAnnotation`` of the same name, so whenever a device trace is being
taken the program's spans lie on its host plane, in the device's clock.
Request spans carry the request's trace id; scheduler-iteration,
set-up and train-step spans carry none. ``spans_between()`` is the one
reader (the Profiler's Perfetto export and the benchmark's per-layer
metrics go through it); ``dropped_since()`` tells a whole window from
a cut one.

One clock: ``now_ns()`` is ``time.monotonic_ns()``, the clock serving
requests and the benchmark's window are stamped with (on Linux CPython
the same CLOCK_MONOTONIC reading as ``perf_counter_ns``, which the
profiler's host spans use; a tier-1 test holds the two to 1 ms).

Knobs: ``PADDLE_FLIGHT_RECORDER`` = ring capacity (int), or ``off``/
``0`` to disable; ``PADDLE_FLIGHT_RECORDER_DIR`` = dump directory
(default: a per-process dir under the system tempdir — every dump also
prints its path to stderr, so the artifact is findable post-mortem).
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

__all__ = [
    "DECLARED_EVENTS", "DECLARED_SPANS", "EVENT_DOC", "FlightRecorder",
    "Span", "auto_dump", "capacity", "clear", "clock_offset_ns",
    "configure", "disable", "dropped_since", "dump", "dump_dict",
    "enable", "enabled", "events", "identity", "is_enabled", "now_ns",
    "record", "record_span", "set_clock_offset_ns", "span",
    "spans_between", "tail",
]

# The declared event-name families. Every point event recorded through
# this module from inside paddle_tpu/ must use a name from this set —
# the tools/lint rule `event-name` parses this literal (the
# DECLARED_METRICS precedent) and rejects undeclared literals, so a
# typo'd event name can't silently record a stream nobody greps for in
# a post-mortem. Literal span names are held to DECLARED_SPANS below the
# same way; the sampled per-request segments (``req<id>.decode``,
# ``req<id>.prefill_chunk``) are dynamic and exempt. docs/events.md is
# generated from EVENT_DOC and DECLARED_SPANS.
DECLARED_EVENTS = frozenset({
    "jit.compile", "comm.dispatch",
    "train.step_begin", "train.step_end",
    "train.anomaly", "train.anomaly_restore",
    "fit.crash",
    "serve.submit", "serve.evict", "serve.finish",
    "serve.prefill_chunk",
    "serve.preempted", "serve.crash",
    "serve.drain_begin", "serve.drain_end",
    "serve.router.reroute", "serve.router.breaker_open",
    "serve.router.breaker_probe", "serve.router.breaker_close",
    "serve.router.drain", "serve.router.rejoin",
    "watchdog.timeout",
    "resilience.preemption",
    "checkpoint.commit",
    "fleet.clock_sync", "fleet.rank_stale",
    "slo.pending", "slo.firing", "slo.resolved",
    "train.straggler",
})

# name -> one-line description; `python -m tools.metrics_doc` renders
# docs/events.md from this table and a tier-1 drift test keeps the
# committed doc in sync (keys must == DECLARED_EVENTS).
EVENT_DOC = {
    "jit.compile": "a jax.jit cache miss (retrace), with cause/target",
    "comm.dispatch": "an eager collective/p2p dispatch (op, axis, "
                     "bytes)",
    "train.step_begin": "fit() dispatched a train step (step, epoch)",
    "train.step_end": "a loss matured out of the async window (step, "
                      "loss)",
    "train.anomaly": "non-finite loss skipped by the anomaly guard",
    "train.anomaly_restore": "anomaly guard restored the last good "
                             "snapshot",
    "fit.crash": "uncaught exception aborted Model.fit (error)",
    "serve.submit": "a request entered the serving queue (req)",
    "serve.evict": "an in-flight request was evicted (req, slot, "
                   "reason, tokens)",
    "serve.finish": "a request reached a terminal status (req, "
                    "status, tokens)",
    "serve.prefill_chunk": "one chunked-prefill chunk landed in the KV "
                           "cache (req, slot, chunk, start, tokens, "
                           "remaining)",
    "serve.preempted": "preemption observed mid-serve (in_flight)",
    "serve.crash": "uncaught exception in serve_forever (error)",
    "serve.drain_begin": "graceful drain started (queued, in_flight)",
    "serve.drain_end": "graceful drain finished",
    "serve.router.reroute": "the router re-routed a request to the "
                            "next-best replica (rid, src, dst, reason)",
    "serve.router.breaker_open": "a replica's circuit breaker tripped "
                                 "OPEN (replica, cause, backoff_s, "
                                 "trips)",
    "serve.router.breaker_probe": "a half-open breaker admitted its "
                                  "single probe request (replica, rid)",
    "serve.router.breaker_close": "a probe succeeded; the breaker "
                                  "closed and the replica rejoined "
                                  "rotation (replica)",
    "serve.router.drain": "the router drained a replica for a rolling "
                          "deploy (replica, queued, in_flight)",
    "serve.router.rejoin": "a replica (re)joined the router's rotation "
                           "(replica, replicas)",
    "watchdog.timeout": "a hang watchdog expired (label, timeout_s)",
    "resilience.preemption": "preemption landed at a step boundary "
                             "(step, source=signal|store)",
    "checkpoint.commit": "a checkpoint step's commit marker was "
                         "written (step)",
    "fleet.clock_sync": "fleet clock handshake result (offset_ns, "
                        "rtt_ns vs the TCPStore master clock)",
    "fleet.rank_stale": "the fleet aggregator marked a rank stale "
                        "(rank, incarnation, age_s)",
    "slo.pending": "an SLO's fast-window burn rate crossed 1.0 (slo, "
                   "scope, burn_fast, burn_slow, measured)",
    "slo.firing": "an SLO's fast AND slow burn rates crossed 1.0 — "
                  "the alert pages (slo, scope, burn_fast, burn_slow, "
                  "measured)",
    "slo.resolved": "a firing SLO's fast window went clean (slo, "
                    "scope, firing_s)",
    "train.straggler": "the robust z-score straggler detector flagged "
                       "or cleared a rank (rank, phase, z, mean_s, "
                       "median_s)",
}

# The declared span names, one line each (name -> where it is opened,
# what it covers, its fields). The lint's `event-name` rule holds every
# literal name passed to ``span()`` / ``record_span()`` under
# paddle_tpu/ to the keys of this table, and `python -m
# tools.metrics_doc` renders it into docs/events.md.
DECLARED_SPANS = {
    "serve.step": "one ServingEngine.step() under the pump lock "
                  "(decode = decode steps it dispatched: 2 when its poll "
                  "ran one ahead of its read, queued, live)",
    "serve.admit": "one request's admission inside serve.step: host "
                   "prep, prefill dispatch, the admit program's dispatch; "
                   "the wait for the prefill's token follows the "
                   "iteration's decode dispatch, a serve.sync under "
                   "serve.step "
                   "(req, slot, bucket, prompt; state_bytes = bytes of "
                   "per-lane state, over every state array, that the "
                   "admit program installs beside the KV row, 0 for a "
                   "model that keeps none; chunks when the "
                   "prefill is chunked: the span then covers the "
                   "reservation only)",
    "serve.sync": "one blocking device read (site = prefill / chunk / "
                  "poll / row / stats; steps_queued = decode steps "
                  "dispatched before the program it waits for and not "
                  "seen to land by an earlier sync: what the read waits "
                  "behind; ahead = device programs dispatched after the "
                  "one it waits for: 0 = the device idles when the read "
                  "returns)",
    "serve.dispatch": "the decode step's exe(...) call inside "
                      "serve.step",
    "serve.poll": "one scheduler poll inside serve.step (steps = decode "
                  "steps its read covers, not the one a full engine "
                  "dispatches ahead of the read, emitted = tokens the lanes advanced "
                  "since the last poll, admitted = lanes polled for the "
                  "first time, completed, evicted, live; under block "
                  "diffusion also forwards = lane-forwards and commits = "
                  "blocks committed since the last poll; in every step "
                  "mode moe_rows = rows the dropless expert layers "
                  "computed since the last poll, where there are any)",
    "serve.queue_wait": "every request: submit -> popped from the queue, "
                        "which is its admitted_at whether or not the "
                        "prefill then succeeds (trace id; req, bucket; "
                        "status when it never left the queue)",
    "serve.prefill": "every request whose prefill landed: popped from "
                     "the queue -> first token on the host, its "
                     "first_token_at (trace id; req, bucket)",
    "setup.engine_init": "ServingEngine.__init__, warm-up included",
    "setup.state": "precision cast / weight snapshot inside "
                   "setup.engine_init",
    "setup.cache_alloc": "host-built KV cache and lane buffers and "
                         "their device_put inside setup.engine_init "
                         "(bytes = cache and lanes; kv_bytes = the KV half "
                         "of the cache, pages or rows, tables and lengths; "
                         "state_bytes = the per-lane states a hybrid cache "
                         "holds beside it, summed over its state arrays; "
                         "the transfer is not awaited)",
    "setup.warmup": "ServingEngine.warmup(): every program the "
                    "scheduler can dispatch",
    "jit.program": "one AOT program built or loaded by jit.compile_cache"
                   ", or a TrainStep call that compiled (label, source = "
                   "store / persistent_cache / compile, lower_s, bytes)",
    "train.step": "host side of one TrainStep / DistributedTrainStep "
                  "call: argument flattening, tracker, dispatch "
                  "(compiled=1 if this call built a program)",
}

# set-up plus a 51 s window plus its 60 s grace of the busiest benchmark
# cell: ~9.5 scheduler iterations/s x 2.5 spans (step, dispatch, a poll
# and its sync every 4th) + 1.2 requests/s x 7 (submit, finish events;
# admit, sync, queue_wait, prefill spans; sampled decode segments) +
# compiles ~ 60 events/s x 180 s ~ 11k, with a factor of 4 to spare and
# rounded up to a power of two
DEFAULT_CAPACITY = 65536
# auto-dumps are capped per process: a watchdog storm must not write
# hundreds of files or spend its dying seconds serializing JSON
MAX_AUTO_DUMPS = 16
# ... and each writes the newest events only (the ring's size before it
# grew to hold a benchmark window: the seconds before the death are what
# a post-mortem reads); dump() / dump_dict() on demand write everything
AUTO_DUMP_EVENTS = 4096

enabled = True  # module-global fast path; read unlocked on purpose

# the one clock: CLOCK_MONOTONIC, the clock serving requests and the
# benchmark's window are stamped with (time.monotonic() is the same
# reading as a float)
now_ns = time.monotonic_ns

# wall-clock anchor so dumps can print absolute times while events carry
# the monotonic clock (the key keeps its name: tools/trace_merge reads
# "anchor_perf_ns")
_ANCHOR_WALL_NS = time.time_ns()
_ANCHOR_PERF_NS = now_ns()


def _wall_ns(t_ns: int) -> int:
    return _ANCHOR_WALL_NS + (t_ns - _ANCHOR_PERF_NS)


# this process's measured wall-clock offset vs the fleet's shared
# reference clock (the TCPStore master), in ns — set once by the fleet
# telemetry clock handshake; rides in every dump's metadata so
# tools/trace_merge can align N ranks' timelines
_clock_offset_ns = 0


def set_clock_offset_ns(ns: int) -> None:
    global _clock_offset_ns
    _clock_offset_ns = int(ns)


def clock_offset_ns() -> int:
    return _clock_offset_ns


def identity():
    """This process's fleet identity ``(rank, restart_count, pid)``,
    read from the launcher env contract (both 0 outside a launched
    job). Stamped on dumps — filenames and metadata — NOT on every
    event: identity is constant per process, so per-event stamping
    would only spend ring bytes repeating it (and the disabled-record
    sub-µs gate stays untouched)."""
    def _int(name):
        try:
            return int(os.environ.get(name, "0").strip() or 0)
        except ValueError:
            return 0
    return (_int("PADDLE_TRAINER_ID"), _int("PADDLE_RESTART_COUNT"),
            os.getpid())


class Span(NamedTuple):
    """One completed span as ``spans_between()`` returns it."""
    name: str
    start_ns: int
    end_ns: int
    tid: int
    id: int
    parent: Optional[int]
    trace: Optional[str]
    fields: dict


_SPAN_KEYS = ("name", "end_ns", "tid", "id", "parent", "trace")


class FlightRecorder:
    """The ring itself. One process-global instance (module functions
    below) serves every subsystem; separate instances exist only for
    tests."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._buf: "collections.deque[Tuple[int, str, Optional[dict]]]" \
            = collections.deque(maxlen=max(int(capacity), 1))
        self._dropped = 0  # events evicted by the ring bound
        self._dropped_until_ns = -1  # when the newest evicted one ended
        self._span_ids = itertools.count(1)
        self._auto_dumps = 0
        self._last_auto: Dict[str, float] = {}  # reason -> monotonic ts

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    # ------------------------------------------------------------ record
    def record(self, kind: str, t_ns: Optional[int] = None, **fields):
        """One structured point event. ``fields`` must be cheap,
        JSON-friendly scalars (ints, floats, short strings)."""
        t = now_ns() if t_ns is None else t_ns
        with self._lock:
            self._append((t, kind, fields or None))

    def _append(self, event):
        # callers hold the lock
        buf = self._buf
        if len(buf) == buf.maxlen:
            t, _, f = buf[0]
            self._dropped += 1
            self._dropped_until_ns = max(
                self._dropped_until_ns,
                f["end_ns"] if f and "end_ns" in f else t)
        buf.append(event)

    def record_span(self, name: str, start_ns: int, end_ns: int,
                    trace_id: Optional[str] = None, tid: int = 0,
                    parent: Optional[int] = None,
                    span_id: Optional[int] = None, **fields) -> int:
        """One completed span; returns its id (``span_id`` when the
        caller drew one from ``_span_ids`` at the span's start, as
        ``span()`` does so that children can name it). ``parent`` is the
        id of the span that caused it. Stored as a ``"span"`` event at
        its START time so the ring stays roughly time-ordered and the
        plaintext tail reads chronologically."""
        f = fields      # ours already: ** built it for this call
        f["name"] = name
        f["end_ns"] = end_ns
        f["tid"] = tid
        f["id"] = next(self._span_ids) if span_id is None else span_id
        if parent is not None:
            f["parent"] = parent
        if trace_id is not None:
            f["trace"] = trace_id
        with self._lock:
            self._append((start_ns, "span", f))
        return f["id"]

    # -------------------------------------------------------------- read
    def events(self, last: Optional[int] = None) \
            -> List[Tuple[int, str, Optional[dict]]]:
        """The ring, oldest first; the newest ``last`` events only when
        given."""
        with self._lock:
            if last is None or last >= len(self._buf):
                return list(self._buf)
            return list(itertools.islice(reversed(self._buf),
                                         last))[::-1]

    def clear(self):
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._dropped_until_ns = -1

    def spans_between(self, t0_ns: int, t1_ns: int) -> List[Span]:
        """Completed spans overlapping [t0_ns, t1_ns], in the order they
        were recorded (a child before its parent): the one reader of
        spans — the Profiler's Perfetto export and the benchmark's
        per-layer metrics."""
        out = []
        for t, kind, f in self.events():
            if kind != "span" or f is None:
                continue
            end = f["end_ns"]
            if end < t0_ns or t > t1_ns:
                continue
            out.append(Span(
                f["name"], t, end, f.get("tid", 0), f.get("id", 0),
                f.get("parent"), f.get("trace"),
                {k: v for k, v in f.items() if k not in _SPAN_KEYS}))
        return out

    def dropped_since(self, t0_ns: int) -> int:
        """0 when everything that ended at or after ``t0_ns`` is still
        in the ring; else how many events the ring has evicted so far
        (it evicts oldest first, so an interval that starts after the
        newest evicted event ended is whole)."""
        with self._lock:
            return self._dropped if self._dropped_until_ns >= t0_ns \
                else 0

    # -------------------------------------------------------------- dump
    def to_perfetto(self, last: Optional[int] = None) -> dict:
        """The ring (its newest ``last`` events when given) as a
        chrome://tracing / Perfetto JSON dict: point events become
        ``"ph": "i"`` instants, spans become ``"ph": "X"`` slices, all
        under this process's real pid (multi-host dumps stay mergeable,
        the PR-2 exporter contract)."""
        rank, restart, pid = identity()
        trace_events = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"rank{rank}.{restart} "
                              f"flightrecorder_{pid}"}}]
        for t, kind, f in self.events(last):
            if kind == "span" and f is not None:
                args = {k: v for k, v in f.items()
                        if k not in ("name", "end_ns", "tid")}
                trace_events.append(
                    {"name": f["name"], "ph": "X", "cat": "flight",
                     "ts": t / 1000.0,
                     "dur": max(f["end_ns"] - t, 0) / 1000.0,
                     "pid": pid, "tid": f.get("tid", 0),
                     **({"args": args} if args else {})})
            else:
                trace_events.append(
                    {"name": kind, "ph": "i", "s": "p", "cat": "flight",
                     "ts": t / 1000.0, "pid": pid, "tid": 0,
                     **({"args": f} if f else {})})
        return {"traceEvents": trace_events,
                "metadata": {"dropped_events": self._dropped,
                             "capacity": self.capacity,
                             # fleet identity + clock mapping: what
                             # tools/trace_merge keys tracks on and
                             # uses to convert perf ts -> aligned wall
                             "rank": rank, "restart_count": restart,
                             "clock_offset_ns": _clock_offset_ns,
                             "anchor_wall_ns": _ANCHOR_WALL_NS,
                             "anchor_perf_ns": _ANCHOR_PERF_NS}}

    def tail(self, n: int = 64) -> str:
        """Plaintext rendering of the last ``n`` events — the part of a
        dump a human reads first."""
        evs = self.events(n)
        lines = []
        for t, kind, f in evs:
            wall = _wall_ns(t) / 1e9
            frac = f"{wall % 1:.6f}"[1:]
            stamp = time.strftime("%H:%M:%S", time.localtime(wall)) + frac
            if kind == "span" and f is not None:
                dur_ms = max(f["end_ns"] - t, 0) / 1e6
                extra = " ".join(
                    f"{k}={v}" for k, v in f.items()
                    if k not in ("name", "end_ns", "tid"))
                lines.append(f"{stamp} span {f['name']} "
                             f"dur={dur_ms:.3f}ms {extra}".rstrip())
            else:
                extra = " ".join(f"{k}={v}" for k, v in (f or {}).items())
                lines.append(f"{stamp} {kind} {extra}".rstrip())
        return "\n".join(lines)

    def dump_dict(self, reason: str = "manual",
                  last: Optional[int] = None) -> dict:
        """The dump as one JSON-friendly dict (what ``/flightrecorder``
        serves): Perfetto trace + plaintext tail + bookkeeping."""
        d = self.to_perfetto(last)
        d["metadata"].update(reason=reason, pid=os.getpid(),
                             wall_time_ns=time.time_ns(),
                             events=len(self._buf))
        d["tail"] = self.tail().splitlines()
        return d

    def dump(self, path_prefix: Optional[str] = None,
             reason: str = "manual", last: Optional[int] = None) -> str:
        """Write ``{prefix}.json`` (Perfetto-compatible) and
        ``{prefix}.txt`` (plaintext tail); returns the JSON path. The
        default prefix lands in ``PADDLE_FLIGHT_RECORDER_DIR`` (or a
        per-process tempdir) and is announced on stderr — a dying
        process must leave a findable artifact."""
        if path_prefix is None:
            d = os.environ.get("PADDLE_FLIGHT_RECORDER_DIR", "").strip() \
                or os.path.join(tempfile_dir(),
                                f"paddle_flightrecorder_{os.getpid()}")
            # (rank, restart_count, pid) in the name: N processes
            # sharing one PADDLE_FLIGHT_RECORDER_DIR (the fleet
            # post-mortem layout trace_merge consumes) never clobber
            # each other's dumps, and a relaunched incarnation never
            # clobbers its predecessor's
            rank, restart, pid = identity()
            path_prefix = os.path.join(
                d, f"flightrecorder_{reason}_r{rank}i{restart}"
                   f"_p{pid}_{time.time_ns()}")
        os.makedirs(os.path.dirname(os.path.abspath(path_prefix)),
                    exist_ok=True)
        json_path = path_prefix + ".json"
        with open(json_path, "w") as f:
            json.dump(self.dump_dict(reason, last), f)
        with open(path_prefix + ".txt", "w") as f:
            rank, restart, pid = identity()
            f.write(f"flight recorder dump — reason: {reason}, "
                    f"rank: {rank}, incarnation: {restart}, "
                    f"pid: {pid}, "
                    f"dropped: {self._dropped}\n")
            f.write(self.tail())
            f.write("\n")
        sys.stderr.write(f"flight recorder dumped ({reason}) to "
                         f"{json_path}\n")
        return json_path

    def auto_dump(self, reason: str, min_interval_s: float = 5.0) \
            -> Optional[str]:
        """Crash-path dump: rate-limited per reason and capped per
        process, and NEVER raises — the recorder must not turn a dying
        process's last act into a second failure; writes the newest
        ``AUTO_DUMP_EVENTS`` events. Counts through
        ``monitor.record_flight_dump`` so dashboards see that a dump
        happened even if nobody fetches the file."""
        if not enabled:
            return None
        now = time.monotonic()
        with self._lock:
            if self._auto_dumps >= MAX_AUTO_DUMPS:
                return None
            last = self._last_auto.get(reason)
            if last is not None and now - last < min_interval_s:
                return None
            self._auto_dumps += 1
            self._last_auto[reason] = now
        try:
            path = self.dump(reason=reason, last=AUTO_DUMP_EVENTS)
            from . import monitor
            # counted only AFTER the file exists: the metric documents
            # dumps WRITTEN, and an operator chasing it must find one
            monitor.record_flight_dump(reason)
            return path
        except Exception as e:  # noqa: BLE001 — crash path, observably
            try:
                from . import monitor
                monitor.record_swallowed("flight_recorder.dump", e)
            except Exception:
                pass  # lint: bare-except-ok — nothing below us to tell
            return None


def tempfile_dir() -> str:
    import tempfile
    return tempfile.gettempdir()


# ------------------------------------------------------ process singleton

def _env_capacity() -> Tuple[bool, int]:
    raw = os.environ.get("PADDLE_FLIGHT_RECORDER", "").strip().lower()
    if raw in ("off", "0", "false", "no"):
        return False, DEFAULT_CAPACITY
    try:
        cap = int(raw) if raw else DEFAULT_CAPACITY
    except ValueError:
        cap = DEFAULT_CAPACITY
    return True, max(cap, 1)


_on, _cap = _env_capacity()
enabled = _on
_recorder = FlightRecorder(_cap)


def configure(capacity: Optional[int] = None,
              on: Optional[bool] = None) -> FlightRecorder:
    """Re-size / toggle the process recorder. Passing a capacity builds
    a FRESH ring (drops history and the auto-dump rate-limit state —
    what tests want between scenarios)."""
    global _recorder, enabled
    if capacity is not None:
        _recorder = FlightRecorder(capacity)
    if on is not None:
        enabled = bool(on)
    return _recorder


def recorder() -> FlightRecorder:
    return _recorder


def enable():
    global enabled
    enabled = True


def disable():
    global enabled
    enabled = False


def is_enabled() -> bool:
    return enabled


def record(kind: str, **fields):
    """Module-level fast path: ``flight_recorder.record("serve.admit",
    req=3, slot=1)``. First action is the bool check — the disabled
    cost is the call itself."""
    if not enabled:
        return
    _recorder.record(kind, **fields)


def record_span(name: str, start_ns: int, end_ns: int,
                trace_id: Optional[str] = None, tid: int = 0,
                parent: Optional[int] = None, **fields):
    if not enabled:
        return None
    return _recorder.record_span(name, start_ns, end_ns,
                                 trace_id=trace_id, tid=tid,
                                 parent=parent, **fields)


_tls = threading.local()   # .span: the innermost open span of a thread
_annotation = None         # jax.profiler.TraceAnnotation, bound lazily


class _OpenSpan:
    """A span being timed by ``span()``. ``start_ns`` / ``end_ns`` are
    the one stamp of each boundary: callers derive their own timings
    from them instead of reading the clock again."""
    __slots__ = ("name", "id", "parent", "start_ns", "end_ns", "fields",
                 "_rec", "_ann", "_outer")

    def __init__(self, name, fields):
        self.name = name
        self.fields = fields
        self.end_ns = 0

    def set(self, **fields):
        """Counts known only at the span's end."""
        self.fields.update(fields)

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        self._rec = _recorder
        self.id = next(self._rec._span_ids)
        self._outer = getattr(_tls, "span", None)
        self.parent = None if self._outer is None else self._outer.id
        _tls.span = self
        # the fields known at the start ride along as the event's stats
        self._ann = _annotation(self.name, **self.fields)
        self._ann.__enter__()
        self.start_ns = now_ns()
        return self

    def __exit__(self, et, ev, tb):
        self.end_ns = now_ns()
        self._ann.__exit__(et, ev, tb)
        _tls.span = self._outer
        self._rec.record_span(self.name, self.start_ns, self.end_ns,
                              parent=self.parent, span_id=self.id,
                              **self.fields)
        return False


class _NoSpan:
    """What ``span()`` hands out while the recorder is off: nothing is
    stamped (``start_ns`` / ``end_ns`` read 0, so ``sp.end_ns or
    now_ns()`` is the caller's one stamp either way)."""
    __slots__ = ()
    id = parent = None
    start_ns = end_ns = 0

    def set(self, **fields):
        pass

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return False


_NO_SPAN = _NoSpan()


def span(name: str, **fields):
    """``with flight_recorder.span("serve.poll") as sp: ...`` — open a
    span around a block: it is the parent of every span opened inside
    it on this thread, a ``jax.profiler.TraceAnnotation`` of the same
    name (so it lies on the host plane of a device trace being taken),
    and lands in the ring when the block ends. Off: one bool check."""
    if not enabled:
        return _NO_SPAN
    return _OpenSpan(name, fields)


def events(last: Optional[int] = None) \
        -> List[Tuple[int, str, Optional[dict]]]:
    return _recorder.events(last)


def clear():
    _recorder.clear()


def capacity() -> int:
    return _recorder.capacity


def spans_between(t0_ns: int, t1_ns: int) -> List[Span]:
    return _recorder.spans_between(t0_ns, t1_ns)


def dropped_since(t0_ns: int) -> int:
    return _recorder.dropped_since(t0_ns)


def tail(n: int = 64) -> str:
    return _recorder.tail(n)


def dump(path_prefix: Optional[str] = None, reason: str = "manual") -> str:
    return _recorder.dump(path_prefix, reason=reason)


def dump_dict(reason: str = "manual") -> dict:
    return _recorder.dump_dict(reason)


def auto_dump(reason: str, min_interval_s: float = 5.0) -> Optional[str]:
    return _recorder.auto_dump(reason, min_interval_s=min_interval_s)

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

A scheduler iteration that runs long says what it waited for. Every
span boundary (open or close) is stamped per thread; a boundary that
comes more than ``STALL_NS`` after the last one, with a ``serve.step``
open on its thread, leaves a record, and the watcher (one daemon thread,
started with the first ``serve.step``, awake every 50 ms) turns it into
one ``serve.stall`` event: the silent stretch, the span it lay in, the
garbage collector's part of it, and what every thread's stack showed
while it lasted (``sys._current_frames()`` sampled by the watcher, which
it can do only while something lets the interpreter go: a stall under
the interpreter lock has no samples, and that is a finding too). Then
one line through the logger and an ``auto_dump``. ``gc.callbacks`` put
every collection on the same clock: a long or full one is a ``host.gc``
span, and ``gc_ns()`` is the running total ``serve.step`` closes with.

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
import gc
import itertools
import json
import linecache
import os
import re
import sys
import threading
import time
import weakref
from typing import Dict, List, NamedTuple, Optional, Tuple

__all__ = [
    "DECLARED_EVENTS", "DECLARED_SPANS", "EVENT_DOC", "FlightRecorder",
    "Span", "auto_dump", "capacity", "clear", "clock_offset_ns",
    "configure", "disable", "dropped_since", "dump", "dump_dict",
    "STALL_NS", "enable", "enabled", "events", "gc_ns", "identity",
    "is_enabled", "now_ns", "record", "record_span",
    "set_clock_offset_ns", "span", "spans_between", "tail",
    "thread_stacks",
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
    "serve.preempted", "serve.crash", "serve.stall",
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
    "serve.stall": "no span boundary for over 250 ms on a thread with a "
                   "serve.step open, stamped when the boundary came (ms = "
                   "the silent stretch; span = the innermost span open "
                   "through it, with its site / program / steps_queued / "
                   "ahead; thread; gc_ms = collections inside it; "
                   "samples = times the watcher saw every thread's stack "
                   "while it lasted, 0 when the interpreter lock was "
                   "held throughout; top = that thread's most frequent "
                   "innermost frame and stack = the frames beneath it; "
                   "others = threads seen running, not waiting, in half "
                   "the samples or more; late_ms = the watcher's own "
                   "longest oversleep while it lasted: near ms when "
                   "whatever held the thread held the watcher too; "
                   "cpu_ms / thread_cpu_ms = CPU time the process / the "
                   "thread itself used from the watcher's last look "
                   "before it to its report: near 0 = blocked, not "
                   "computing)",
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
                  "ran one ahead of its read, queued, live; gc_ms = the "
                  "garbage collections that ended inside it, on any "
                  "thread: 0.0 when there was none)",
    "serve.plan": "the head request's page plan and its commit in "
                  "_pop_queue, under serve.step: the prompt's prefix "
                  "hashed against the registry, its pages taken from the "
                  "pool (req, pages, shared = prompt positions found "
                  "there; blocked=1 when the pool was too full and the "
                  "request stays queued); not opened while the pool is "
                  "as it was at the last failure",
    "serve.admit": "one request's admission inside serve.step: host "
                   "prep (its self time), the prefill's and the admit "
                   "program's serve.dispatch; "
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
    "serve.dispatch": "one program's exe(...) call inside serve.step, "
                      "host side only, nothing waits (program = step / "
                      "prefill / admit / poll_view / free / chunk / "
                      "chunk_final / install_span: the program table's "
                      "key; a decode step is program=step in every step "
                      "mode)",
    "serve.poll": "one scheduler poll inside serve.step (steps = decode "
                  "steps its read covers, not the one a full engine "
                  "dispatches ahead of the read, emitted = tokens the lanes advanced "
                  "since the last poll, admitted = lanes polled for the "
                  "first time, completed, evicted, live; under block "
                  "diffusion also forwards = lane-forwards and commits = "
                  "blocks committed since the last poll; in every step "
                  "mode moe_rows = rows the dropless expert layers "
                  "computed since the last poll, where there are any)",
    "serve.telemetry": "what a poll does for the monitor and not for "
                       "the scheduler, inside serve.poll after the lanes "
                       "are handled: token latency, per-request cost "
                       "attribution, goodput charge and flush, cache and "
                       "page occupancy, quantization clips, slo.tick()",
    "host.gc": "one garbage collection of generation 2, or of any "
               "generation that took over 1 ms, from gc.callbacks: child "
               "of whatever span was open on the thread it ran on (gen, "
               "collected, thread)",
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

# One whole run of a benchmark cell (set-up, the 51 s window, its grace),
# four times over: the readers of set-up need the whole process in the
# ring, and a window the ring cut reads None in every span metric at
# once. Counted on the chip at PR 36, with the iteration's finer spans:
# nemotron3n-l13-offline 37,382 events a run (256 lanes whose sampled
# requests leave a decode segment a poll: 14,649 of them),
# sdar-l6-offline 32,375-35,134 (34 runs), gpt3l8-offline 30,328,
# lfm2-l14-offline 28,266, gpt3l8-chat 24,079-27,035 (13 runs: 7,600
# iterations of 2 spans and a poll of 5 every 4th);
# four times the busiest rounds up to 2**18. An event is about 410 bytes
# (its tuple, its field dict, its stamps): 107 MB if a long-lived
# process fills the ring, 10-15 MB over a benchmark run; the field dicts
# hold only numbers and strings, which the collector does not track.
DEFAULT_CAPACITY = 262144
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
            # one call into the C encoder: a dump from the stall watcher
            # must not trade the interpreter with the scheduler for long
            f.write(json.dumps(self.dump_dict(reason, last)))
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
        if not enabled:
            _disarm()
    return _recorder


def recorder() -> FlightRecorder:
    return _recorder


def enable():
    global enabled
    enabled = True


def disable():
    global enabled
    enabled = False
    _disarm()


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


# ------------------------------------------------- threads and boundaries

# A span boundary this long after the last one, with a serve.step open,
# is a stall. A constant, not a knob: the longest silent stretch of a
# sound window is a poll's read of four steps (about 100 ms) or a
# 1024-bucket prefill (24-27 ms); set-up's first iteration (the page
# pool's arrival, 22-29 s) fires it once, which is right.
STALL_NS = 250_000_000
_WATCHED = "serve.step"     # the root span a stall is looked for under
_WATCH_S = 0.05             # the watcher's sleep
_MAX_SAMPLES = 40           # of every thread's stack, a stall
_STACK_FRAMES = 12          # innermost frames kept a thread


class _Thread:
    """What the recorder keeps of one thread: ``span``, its innermost
    open span; ``stamp``, its last span boundary (open or close);
    ``silences``, the stalls it has found at its own boundaries and the
    watcher has not yet reported."""
    __slots__ = ("span", "stamp", "ident", "name", "silences",
                 "cpu_clock", "__weakref__")

    def __init__(self):
        t = threading.current_thread()
        self.span = None
        self.stamp = now_ns()
        self.ident, self.name = t.ident, t.name
        self.silences = collections.deque()
        try:    # the thread's own CPU clock, for the watcher to read
            self.cpu_clock = time.pthread_getcpuclockid(t.ident)
        except (AttributeError, OSError):
            self.cpu_clock = None

    def cpu_ns(self) -> int:
        """CPU time this thread has used (0 where it cannot be read)."""
        try:
            return time.clock_gettime_ns(self.cpu_clock)
        except (TypeError, OSError):
            return 0


_tls = threading.local()   # .st: this thread's _Thread
_threads = weakref.WeakSet()   # every live thread's: what the watcher walks
_annotation = None         # jax.profiler.TraceAnnotation, bound lazily


def _this_thread() -> _Thread:
    st = _tls.st = _Thread()
    _threads.add(st)
    return st


def _silent(st: _Thread, t_ns: int, inner) -> None:
    """``st`` stamped no boundary for over STALL_NS until ``t_ns``, with
    ``inner`` its innermost open span all the while: a stall if that was
    inside a serve.step (a silence between iterations is a wait for
    work, or the profiler starting). Left for the watcher to report: the
    scheduler's thread only notes it."""
    if _watch is not None and _in_iteration(inner):
        st.silences.append((st.stamp, t_ns, inner.name,
                            dict(inner.fields)))


def _in_iteration(span) -> bool:
    """Whether ``span``'s outermost ancestor is a serve.step."""
    while span._outer is not None:
        span = span._outer
    return span.name == _WATCHED


class _OpenSpan:
    """A span being timed by ``span()``. ``start_ns`` / ``end_ns`` are
    the one stamp of each boundary: callers derive their own timings
    from them instead of reading the clock again."""
    __slots__ = ("name", "id", "parent", "start_ns", "end_ns", "fields",
                 "_rec", "_ann", "_outer", "_st")

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
        st = self._st = getattr(_tls, "st", None) or _this_thread()
        self._rec = _recorder
        self.id = next(self._rec._span_ids)
        outer = self._outer = st.span
        if outer is not None:
            self.parent = outer.id
        else:
            self.parent = None
            if _watch is None and self.name == _WATCHED:
                _arm()
        st.span = self
        # the fields known at the start ride along as the event's stats
        self._ann = _annotation(self.name, **self.fields)
        self._ann.__enter__()
        t = self.start_ns = now_ns()
        if t - st.stamp > STALL_NS and outer is not None:
            _silent(st, t, outer)
        st.stamp = t
        return self

    def __exit__(self, et, ev, tb):
        st = self._st
        t = self.end_ns = now_ns()
        if t - st.stamp > STALL_NS:
            _silent(st, t, self)
        st.stamp = t
        self._ann.__exit__(et, ev, tb)
        st.span = self._outer
        self._rec.record_span(self.name, self.start_ns, t,
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


# ------------------------------------------------------ garbage collection

GC_SPAN_NS = 1_000_000     # a collection this long is a host.gc span
_gc_total_ns = 0           # every finished collection's time, summed
_gc_t0 = 0                 # the start of the collection now running
_gc_ann = None             # its TraceAnnotation (generation 2 only)
# the collections that became spans, newest last: (start, end) on the
# recorder's clock, for a stall to find its own among
_gc_spans: "collections.deque[Tuple[int, int]]" = collections.deque(
    maxlen=256)
# host.gc spans (start, fields) not yet in the ring: see _flush_gc
_gc_pending: "collections.deque[Tuple[int, dict]]" = collections.deque(
    maxlen=256)


def gc_ns() -> int:
    """Nanoseconds the garbage collector has run since the recorder's
    callback was registered, over every finished collection on every
    thread (the interpreter runs one at a time, and it stops them all):
    ``serve.step`` closes with the difference across itself."""
    return _gc_total_ns


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: runs on the collecting thread, under
    the interpreter lock, with collection off while it does."""
    global _gc_total_ns, _gc_t0, _gc_ann
    if phase == "start":
        if info["generation"] == 2 and _annotation is not None:
            # a full collection also lies on a device trace's host plane
            _gc_ann = _annotation("host.gc", gen=2)
            _gc_ann.__enter__()
        _gc_t0 = now_ns()
        return
    t1 = now_ns()
    t0, _gc_t0 = _gc_t0, 0
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    if not t0:      # registered while this collection ran
        return
    _gc_total_ns += t1 - t0
    gen = info["generation"]
    if gen == 2 or t1 - t0 > GC_SPAN_NS:
        _gc_spans.append((t0, t1))
        f = {"name": "host.gc", "end_ns": t1, "tid": 0, "gen": gen,
             "collected": info.get("collected", 0),
             "thread": threading.current_thread().name}
        st = getattr(_tls, "st", None)
        if st is not None and st.span is not None:
            f["parent"] = st.span.id
        _gc_pending.append((t0, f))
    if _gc_pending:
        _flush_gc()


def _flush_gc() -> None:
    """Put the pending host.gc spans in the ring. A collection can start
    at any allocation, the ring's own append under its lock among them:
    the callback must not wait for that lock, so it only tries it, and
    what it could not record goes in at the next collection's end or the
    watcher's next wake-up."""
    rec = _recorder
    if rec._lock.acquire(blocking=False):
        try:
            while _gc_pending:
                t0, f = _gc_pending.popleft()
                f["id"] = next(rec._span_ids)
                rec._append((t0, "span", f))
        finally:
            rec._lock.release()


def _gc_ms_between(t0_ns: int, t1_ns: int) -> float:
    """The part of ``[t0_ns, t1_ns]`` that host.gc collections cover."""
    return sum(max(min(e, t1_ns) - max(b, t0_ns), 0)
               for b, e in list(_gc_spans)) / 1e6


# ------------------------------------------------------------- the watcher

def _frames(frame, limit: Optional[int]) -> List[tuple]:
    """``(file, line, function)`` of ``frame`` and its callers,
    innermost first, ``limit`` at most."""
    out = []
    while frame is not None and (limit is None or len(out) < limit):
        code = frame.f_code
        out.append((code.co_filename, frame.f_lineno, code.co_name))
        frame = frame.f_back
    return out


def _frame_text(fr: tuple) -> str:
    return f"{fr[0]}:{fr[1]} {fr[2]}"


def _other_threads(limit: Optional[int]):
    """``(ident, name, frames)`` of every thread but the caller's."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    for tid, frame in sys._current_frames().items():
        if tid != me:
            yield tid, names.get(tid, "?"), _frames(frame, limit)


def thread_stacks(limit: Optional[int] = None) -> Dict[str, List[str]]:
    """Every thread's stack but the caller's, as ``file:line function``
    lines, innermost first, ``limit`` frames at most; keyed
    ``name (ident)``. What the watchdog dumps and the stall watcher
    samples."""
    return {f"{name} ({tid})": [_frame_text(fr) for fr in frames]
            for tid, name, frames in _other_threads(limit)}


# an innermost frame that is a thread parked, not one at work: by the
# standard library's function, or by what its source line calls
_WAIT_FUNCS = {("threading.py", "wait"), ("threading.py", "acquire"),
               ("threading.py", "join"),
               ("threading.py", "_wait_for_tstate_lock"),
               ("queue.py", "get"), ("queue.py", "put"),
               ("selectors.py", "select"), ("socket.py", "accept")}
_WAIT_CALL = re.compile(
    r"\b(sleep|wait|wait_for|acquire|select|get|join|accept|recv)\(")


def _is_wait(fr: tuple) -> bool:
    if (os.path.basename(fr[0]), fr[2]) in _WAIT_FUNCS:
        return True
    return bool(_WAIT_CALL.search(linecache.getline(fr[0], fr[1])))


class _Samples:
    """Every thread's stack, as the watcher saw it while one silence
    (the one that began at ``t0_ns``) lasted."""

    def __init__(self, t0_ns: int, late_ns: int, cpu0: Tuple[int, int]):
        self.t0_ns = t0_ns
        self.n = 0
        self.stacks: Dict[int, collections.Counter] = {}
        self.names: Dict[int, str] = {}
        self.late_ns = late_ns      # the watcher's own longest oversleep
        self.cpu0 = cpu0            # (process, thread) CPU before it

    def take(self) -> None:
        for tid, name, frames in _other_threads(_STACK_FRAMES):
            self.names[tid] = name
            self.stacks.setdefault(tid, collections.Counter())[
                tuple(frames)] += 1
        self.n += 1

    def fields(self, ident: int) -> dict:
        """``top`` / ``stack`` of thread ``ident``, ``others`` that were
        at work in half the samples or more."""
        out = {"samples": self.n}
        mine = self.stacks.get(ident)
        if mine:
            stack = mine.most_common(1)[0][0]
            inner = collections.Counter()
            for frames, n in mine.items():
                inner[frames[0]] += n
            out["top"] = _frame_text(inner.most_common(1)[0][0])
            out["stack"] = " < ".join(_frame_text(f) for f in stack)
        others = []
        for tid, seen in self.stacks.items():
            if tid == ident:
                continue
            busy = collections.Counter()
            for frames, n in seen.items():
                if not _is_wait(frames[0]):
                    busy[frames[0]] += n
            if busy and 2 * sum(busy.values()) >= self.n:
                others.append(f"{self.names.get(tid, '?')}: "
                              + _frame_text(busy.most_common(1)[0][0]))
        out["others"] = "; ".join(sorted(others))
        return out


class _Watch:
    """The recorder's one watcher thread. Asleep ``_WATCH_S`` at a time;
    awake it reads each thread's last boundary stamp and innermost span.
    While a thread with a ``serve.step`` open has stamped nothing for
    ``STALL_NS`` it samples every thread's stack; when that thread's own
    next boundary has noted the silence (``_silent``) it records the
    ``serve.stall`` event, logs one line and dumps the ring."""

    def __init__(self):
        self._stop = threading.Event()
        self._sampling: "weakref.WeakKeyDictionary[_Thread, _Samples]" \
            = weakref.WeakKeyDictionary()
        # (process, thread) CPU time at the last wake-up that found the
        # thread stamping, or between iterations: what a silence's CPU
        # is counted from
        self._cpu0: "weakref.WeakKeyDictionary[_Thread, Tuple[int, int]]" \
            = weakref.WeakKeyDictionary()
        self._thread = threading.Thread(
            target=self._loop, name="flight-recorder-watch", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        t_last = now_ns()
        while not self._stop.wait(_WATCH_S):
            if _gc_pending:
                _flush_gc()
            t = now_ns()
            late = max(t - t_last - int(_WATCH_S * 1e9), 0)
            t_last = t
            for st in list(_threads):
                try:
                    self._look(st, t, late)
                except Exception as e:  # noqa: BLE001 — the watcher lives
                    from . import monitor
                    monitor.record_swallowed("flight_recorder.watch", e)

    def _look(self, st: _Thread, t_ns: int, late_ns: int) -> None:
        seen = self._sampling.get(st)
        if seen is not None:
            seen.late_ns = max(seen.late_ns, late_ns)
        while st.silences:
            t0, t1, name, fields = st.silences.popleft()
            if seen is None or seen.t0_ns != t0:
                # never awake inside it: whatever held the scheduler's
                # thread held the watcher too
                seen = _Samples(t0, late_ns, self._cpu0.get(st, (0, 0)))
            self._report(st, t0, t1, name, fields, seen)
        stamp, inner = st.stamp, st.span
        if inner is None or t_ns - stamp < STALL_NS:
            if seen is not None:
                self._sampling.pop(st, None)
            if inner is None or t_ns - stamp < int(_WATCH_S * 1e9):
                self._cpu0[st] = (time.process_time_ns(), st.cpu_ns())
            return
        if not _in_iteration(inner):
            return
        if seen is None or seen.t0_ns != stamp:
            seen = self._sampling[st] = _Samples(
                stamp, late_ns, self._cpu0.get(st, (0, 0)))
        if seen.n < _MAX_SAMPLES:
            seen.take()

    def _report(self, st, t0, t1, name, fields, seen) -> None:
        ev = {"ms": round((t1 - t0) / 1e6, 3), "span": name,
              "thread": st.name}
        ev.update((k, fields[k]) for k in
                  ("site", "program", "steps_queued", "ahead")
                  if k in fields)
        ev["gc_ms"] = round(_gc_ms_between(t0, t1), 3)
        ev["late_ms"] = round(seen.late_ns / 1e6, 3)
        if seen.cpu0 != (0, 0):
            ev["cpu_ms"] = round(
                (time.process_time_ns() - seen.cpu0[0]) / 1e6, 3)
            ev["thread_cpu_ms"] = round(
                (st.cpu_ns() - seen.cpu0[1]) / 1e6, 3)
        ev.update(seen.fields(st.ident))
        _recorder.record("serve.stall", t_ns=t1, **ev)
        import logging
        logging.getLogger("paddle_tpu.flight_recorder").warning(
            "serve.stall: %s", " ".join(f"{k}={v}" for k, v in ev.items()
                                        if k != "stack"))
        _recorder.auto_dump("serve_stall")


_watch: Optional[_Watch] = None
_arm_lock = threading.Lock()


def _arm() -> None:
    """The first ``serve.step`` of an enabled recorder: start the
    watcher, put the collector on the record."""
    global _watch
    with _arm_lock:
        if _watch is None and enabled:
            if _on_gc not in gc.callbacks:
                gc.callbacks.append(_on_gc)
            _watch = _Watch()


def _disarm() -> None:
    """The recorder is off: no watcher, nothing of ours in
    gc.callbacks."""
    global _watch
    with _arm_lock:
        if _watch is not None:
            _watch.stop()
            _watch = None
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def _forked() -> None:
    """A forked child has no thread but its own, and a lock another
    thread held stays held: start over (its first serve.step, if it
    ever runs one, arms it again)."""
    global _watch, _arm_lock
    _arm_lock = threading.Lock()
    _watch = None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_forked)


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

"""Fleet observability plane: cross-process metrics aggregation over
the TCPStore — one pane of glass for an N-process job.

Reference analog: the reference's ``paddle/fluid/distributed`` layer
spends much of its bulk on controller-side visibility (fleet metrics
tables, barrier/heartbeat monitors, the PSCore dashboards); every
surface we built so far — the PR-2 registry, PR-10's flight recorder
and ``/metrics`` — describes ONE process in isolation. This module
makes the fleet observable before the fleet runtime itself lands, and
deliberately needs NO jax cross-process collectives (the PR-3
capability gap): it rides the TCPStore the launcher already runs and
plain HTTP, so it works fully in CPU CI.

Three legs on one shared ``(rank, incarnation)`` identity:

- **Publisher** (every rank): periodically pushes a delta-encoded
  snapshot of the local metrics registry (``metrics.snapshot_delta``)
  plus a health dict to the store, and stamps a server-clock heartbeat
  (``setts`` — cross-host wall clocks are never compared). Period:
  ``PADDLE_FLEET_METRICS_PERIOD_S`` (default 2s).
- **Aggregator** (elected: the launch Controller's node, or rank 0):
  merges the per-rank streams into one fleet registry with ``rank=``/
  ``replica=``/``incarnation=`` labels, served by the telemetry
  server at ``/fleet/metrics`` (Prometheus text) and ``/fleet/healthz``
  (per-replica ``ready``/``reason``/``predicted_headroom_bytes``
  rolled up — the ROADMAP item-1 router admission signal). A rank
  that stops publishing within the deadline is marked STALE
  (``fleet.ranks_stale``, ``fleet.rank_up{rank=}`` -> 0) and its last
  series stay visible — never silently dropped: a vanished rank is
  the most important thing on the dashboard.
- **Clock handshake**: each rank estimates its wall-clock offset vs
  the store master via a ping handshake (NTP-style: the minimum-RTT
  sample's midpoint), records it as ``fleet.clock_skew_ns`` and into
  the flight recorder's dump metadata, so ``tools/trace_merge`` can
  align N per-rank post-mortems onto one timeline.

Delta protocol: each publish carries ``seq`` and either a full
snapshot (first publish, or on resync) or per-metric deltas. The
aggregator applies ``seq == last+1`` deltas, ignores re-reads of the
same ``seq``, and on any gap (missed payload, aggregator restart, new
incarnation) writes a resync key the publisher answers with a full
snapshot — the merged view can never silently drift.
"""
from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

from ..core import flight_recorder, metrics, monitor, slo, timeseries

__all__ = [
    "FleetAggregator", "FleetIdentity", "FleetMember",
    "MetricsPublisher", "estimate_clock_offset_ns", "local_identity",
    "start", "start_from_env",
]

DEFAULT_PERIOD_S = 2.0
# a rank is stale after this many publish periods without a heartbeat
STALE_PERIODS = 3.0


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        v = float(raw)
        if v > 0:
            return v
        raise ValueError(raw)
    except ValueError as e:
        monitor.record_swallowed(f"fleet.env:{name}", e)
        return default


@dataclass(frozen=True)
class FleetIdentity:
    """The shared identity every leg keys on: launcher rank, elastic
    incarnation (PADDLE_RESTART_COUNT), replica label, pid."""
    rank: int
    world_size: int
    incarnation: int
    replica: str
    pid: int


def local_identity() -> FleetIdentity:
    rank, restart, pid = flight_recorder.identity()
    try:
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    except ValueError:
        world = 1
    replica = os.environ.get("PADDLE_REPLICA_ID", "").strip()
    if replica and "PADDLE_TRAINER_ID" not in os.environ:
        # N replicas joined by hand (a router's serving fleet, no
        # launcher): everyone would read rank 0 and clobber one
        # stream, so a NUMERIC replica id doubles as the fleet rank
        try:
            rank = int(replica)
        except ValueError:
            pass   # non-numeric replica stays a label; the
            #        aggregator reports the pid collision observably
    replica = replica or str(rank)
    return FleetIdentity(rank=rank, world_size=world,
                         incarnation=restart, replica=replica, pid=pid)


def _namespace(namespace: Optional[str]) -> str:
    if namespace:
        return namespace
    job = os.environ.get("PADDLE_JOB_ID", "default").strip() or "default"
    return f"__fleet/{job}"


def _merge_labels(key: str, extra: Dict[str, str]) -> str:
    """``name{a=b}`` + extra labels -> one sorted labeled key (the
    registry's ``_labeled`` format). Existing labels win on collision:
    a published series already carrying ``rank=`` must not be
    re-attributed to the publisher."""
    if key.endswith("}") and "{" in key:
        base, _, rest = key.partition("{")
        labels = {}
        for kv in rest[:-1].split(","):
            k, _, v = kv.partition("=")
            labels[k] = v
    else:
        base, labels = key, {}
    merged = dict(extra)
    merged.update(labels)
    return metrics._labeled(base, merged)


# -------------------------------------------------------- clock handshake

def estimate_clock_offset_ns(store, samples: int = 5):
    """NTP-style offset of THIS host's wall clock vs the store
    master's: ping ``samples`` times, keep the minimum-RTT sample, and
    assume the server read its clock at the round-trip midpoint.
    Returns ``(offset_ns, rtt_ns)`` — local_wall - offset ≈ master
    wall. Accuracy is bounded by rtt/2 (sub-ms on a LAN), plenty for
    ordering SIGTERM-vs-detection events across ranks."""
    best = None
    for _ in range(max(int(samples), 1)):
        t0 = time.time_ns()
        server_s = store.now()
        t1 = time.time_ns()
        rtt = t1 - t0
        offset = (t0 + t1) // 2 - int(server_s * 1e9)
        if best is None or rtt < best[1]:
            best = (offset, rtt)
    return best


# --------------------------------------------------------------- publisher

class MetricsPublisher:
    """One rank's outbound leg: snapshot_delta -> store, heartbeat,
    health. ``start()`` runs a daemon thread at the publish period;
    ``publish_now()`` is the synchronous form tests (and the drain
    path) call directly."""

    def __init__(self, store, identity: Optional[FleetIdentity] = None,
                 period_s: Optional[float] = None,
                 health_fn: Optional[Callable[[], Dict]] = None,
                 namespace: Optional[str] = None,
                 clock_sync: bool = True):
        self.store = store
        self.identity = identity or local_identity()
        self.period_s = float(period_s) if period_s is not None else \
            _env_float("PADDLE_FLEET_METRICS_PERIOD_S", DEFAULT_PERIOD_S)
        self.health_fn = health_fn
        ns = _namespace(namespace)
        self._key = f"{ns}/m/{self.identity.rank}"
        self._ts_key = f"{ns}/ts/{self.identity.rank}"
        self._resync_key = f"{ns}/resync/{self.identity.rank}"
        self._prev: Optional[Dict[str, dict]] = None
        self._seq = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.clock_offset_ns = 0
        self._clock_sync = bool(clock_sync)
        self._clock_synced = False

    # ------------------------------------------------------------ clock
    def sync_clock(self):
        """Run the ping handshake once: record the offset locally
        (``fleet.clock_skew_ns``), stamp it into the flight recorder's
        dump metadata, and leave a ``fleet.clock_sync`` event in the
        ring so a post-mortem shows the alignment term used."""
        offset, rtt = estimate_clock_offset_ns(self.store)
        self.clock_offset_ns = offset
        self._clock_synced = True
        flight_recorder.set_clock_offset_ns(offset)
        flight_recorder.record("fleet.clock_sync", offset_ns=offset,
                               rtt_ns=rtt)
        monitor.record_clock_skew(self.identity.rank, offset)
        return offset, rtt

    # ---------------------------------------------------------- publish
    def publish_now(self) -> dict:
        """One publish: honor any pending resync request, delta-encode
        the registry, write payload then heartbeat (the aggregator
        reads them in that order). Returns the payload (tests)."""
        with self._lock:
            if not self._clock_synced and self._clock_sync:
                self.sync_clock()
            if self._prev is not None and \
                    self.store.keys(self._resync_key):
                # the aggregator asked: go absolute, and STAY absolute
                # until it has read one full snapshot (it then clears
                # the request). Were the request cleared here, a reader
                # slower than this writer would find the one full
                # payload already overwritten by the next delta, ask
                # again, and never catch up (a loaded host: the
                # publisher's period passes several times a poll)
                self._prev = None
            new_prev, delta = metrics.snapshot_delta(self._prev)
            # the fleet meta-plane (fleet.*) is produced by the
            # aggregator; republishing our local copy would collide
            # with its per-rank labels in the merged view
            delta["metrics"] = {
                k: v for k, v in delta["metrics"].items()
                if not k.startswith("fleet.")}
            ident = self.identity
            payload = {
                "seq": self._seq,
                "rank": ident.rank,
                "incarnation": ident.incarnation,
                "replica": ident.replica,
                "pid": ident.pid,
                "clock_offset_ns": self.clock_offset_ns,
                "delta": delta,
                "health": self._health(),
            }
            self.store.set(self._key, payload)
            # the payload is durably in the store: commit the delta
            # baseline + seq NOW, before the heartbeat. Committing
            # earlier would lose this window's increments forever on a
            # failed set (the next delta, sent under the SAME seq,
            # covers only the newer window yet looks contiguous to the
            # aggregator — the exact silent drift the seq protocol
            # exists to prevent); committing later would re-send a
            # WIDER window under the same seq, which the aggregator's
            # idempotent same-seq drop discards. A failed heartbeat
            # after the commit only delays staleness by one period.
            self._prev = new_prev
            self._seq += 1
            self.store.set_timestamp(self._ts_key)
            monitor.record_fleet_publish()
            return payload

    def _health(self) -> Dict:
        if self.health_fn is None:
            return {"ready": True}
        try:
            return dict(self.health_fn())
        except Exception as e:
            monitor.record_swallowed("fleet.health_fn", e)
            return {"ready": False, "reason": "health_fn error"}

    # --------------------------------------------------------- lifecycle
    def start(self) -> "MetricsPublisher":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"fleet-publish:{self.identity.rank}")
        self._thread.start()
        return self

    def _loop(self):
        # first publish immediately: the aggregator should see a new
        # rank within one poll, not one period later
        while True:
            try:
                self.publish_now()
            except Exception as e:  # store blip: keep the loop alive
                monitor.record_swallowed("fleet.publish", e)
            if self._stop.wait(self.period_s):
                return

    def stop(self, final_publish: bool = True):
        """Stop the thread; by default push one last snapshot so the
        aggregator sees the final counters (a drained replica's last
        numbers are the interesting ones)."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.period_s + 5.0)
        if final_publish:
            try:
                self.publish_now()
            except Exception as e:
                monitor.record_swallowed("fleet.final_publish", e)


# -------------------------------------------------------------- aggregator

@dataclass
class _RankState:
    incarnation: int
    replica: str
    pid: int = 0
    seq: int = -1
    metrics: Dict[str, dict] = field(default_factory=dict)
    health: Dict = field(default_factory=dict)
    clock_offset_ns: int = 0
    age_s: Optional[float] = None
    stale: bool = False
    resync_pending: bool = False


class FleetAggregator:
    """The elected merge point: polls every rank's published stream,
    maintains the fleet registry, and answers the telemetry server's
    ``/fleet/metrics`` / ``/fleet/healthz``."""

    def __init__(self, store, expected_ranks: Optional[int] = None,
                 period_s: Optional[float] = None,
                 stale_after_s: Optional[float] = None,
                 namespace: Optional[str] = None):
        self.store = store
        self.period_s = float(period_s) if period_s is not None else \
            _env_float("PADDLE_FLEET_METRICS_PERIOD_S", DEFAULT_PERIOD_S)
        self.stale_after_s = float(stale_after_s) \
            if stale_after_s is not None \
            else STALE_PERIODS * self.period_s
        if expected_ranks is None:
            try:
                expected_ranks = int(
                    os.environ.get("PADDLE_TRAINERS_NUM", "") or 0) \
                    or None
            except ValueError:
                expected_ranks = None
        self.expected_ranks = expected_ranks
        self._ns = _namespace(namespace)
        self._ranks: Dict[int, _RankState] = {}
        # fleet-scope SLO watchtower: every poll appends the merged
        # (relabeled, deep-copied) per-rank state to a private
        # time-series ring and evaluates the same default specs over
        # it — the fleet face of core.slo; the straggler detector
        # diffs each rank's cumulative train.step_time between polls
        self._slo_ring = timeseries.TimeSeriesRing(period_s=self.period_s)
        self.slo_evaluator = slo.SLOEvaluator(self._slo_ring,
                                              scope="fleet")
        self.straggler = slo.StragglerDetector()
        # _lock guards only the in-memory merged view (held for
        # microseconds); _poll_lock serializes store I/O rounds.
        # Separate so a store outage mid-poll can NEVER block
        # fleet_registry()/healthz() — the scrape threads keep serving
        # the last merged view while the poll waits on its timeouts
        self._lock = threading.Lock()
        self._poll_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_poll = float("-inf")

    # -------------------------------------------------------------- poll
    def poll(self):
        """One aggregation round: read every published payload, apply
        deltas (resync on gaps), refresh staleness from the store's
        OWN clock (heartbeats are server timestamps — rank clocks are
        never compared to each other)."""
        with self._poll_lock:
            self._poll_inner()

    def _poll_inner(self):
        # ---- store I/O phase: NO view lock held
        self._last_poll = time.monotonic()
        try:
            now = self.store.now()
            keys = self.store.keys(f"{self._ns}/m/")
        except (TimeoutError, RuntimeError, OSError) as e:
            monitor.record_swallowed("fleet.aggregate", e)
            return
        payloads = []
        for key in sorted(keys):
            tail = key.rsplit("/", 1)[1]
            try:
                rank = int(tail)
            except ValueError:
                continue
            try:
                payloads.append(
                    (rank, self.store.get(key, timeout=5.0)))
            except (TimeoutError, RuntimeError, OSError) as e:
                monitor.record_swallowed("fleet.read_rank", e)
        with self._lock:
            known = set(self._ranks) | {r for r, _ in payloads}
        ages: Dict[int, Optional[float]] = {}
        for rank in known:
            try:
                ts = self.store.get(f"{self._ns}/ts/{rank}",
                                    timeout=0.25)
                ages[rank] = max(now - float(ts), 0.0)
            except (TimeoutError, RuntimeError, OSError):
                ages[rank] = None
        # ---- merge phase: view lock held, in-memory only
        resyncs, answered = [], []
        with self._lock:
            for rank, payload in payloads:
                if self._apply(rank, payload, resyncs):
                    answered.append(rank)
            stale = 0
            for rank, st in self._ranks.items():
                st.age_s = ages.get(rank)
                was = st.stale
                st.stale = st.age_s is None or \
                    st.age_s > self.stale_after_s
                if st.stale:
                    stale += 1
                    if not was:
                        flight_recorder.record(
                            "fleet.rank_stale", rank=rank,
                            incarnation=st.incarnation,
                            age_s=round(st.age_s, 3)
                            if st.age_s is not None else -1.0)
                monitor.record_fleet_rank_up(rank, st.incarnation,
                                             not st.stale)
                monitor.record_clock_skew(rank, st.clock_offset_ns)
            monitor.record_fleet_ranks(len(self._ranks), stale)
            fleet_state, step_totals = self._fleet_snapshot_locked()
        # ---- watchtower phase: own locks only, store lock released
        self.straggler.observe(step_totals)
        self._slo_ring.sample_state(fleet_state)
        self.slo_evaluator.evaluate()
        # ---- resync writes: store I/O again, lock released. A full
        # snapshot just applied answers any request for one: clear it,
        # so that the rank goes back to deltas
        for rank in answered:
            try:
                self.store.delete(f"{self._ns}/resync/{rank}")
            except (TimeoutError, RuntimeError, OSError) as e:
                monitor.record_swallowed("fleet.resync", e)
        for rank, st in resyncs:
            try:
                self.store.set(f"{self._ns}/resync/{rank}", True)
            except (TimeoutError, RuntimeError, OSError) as e:
                with self._lock:
                    st.resync_pending = False
                monitor.record_swallowed("fleet.resync", e)

    def _fleet_snapshot_locked(self):
        """(relabeled deep-copied mergeable state of every rank's
        series, per-rank cumulative ``train.step_time`` (count, sum))
        — the fleet SLO ring sample and the straggler detector input.
        Caller holds ``self._lock``; records are copied because
        ``apply_delta`` mutates the rank states in place."""
        state: Dict[str, dict] = {}
        totals: Dict[int, tuple] = {}
        for rank, st in self._ranks.items():
            extra = {"rank": str(rank), "replica": st.replica,
                     "incarnation": str(st.incarnation)}
            for key, rec in st.metrics.items():
                out = dict(rec)
                if "counts" in out:
                    out["counts"] = list(out["counts"])
                state[_merge_labels(key, extra)] = out
            rec = st.metrics.get("train.step_time")
            if rec is not None and rec.get("kind") == "histogram":
                totals[rank] = (float(rec.get("count", 0)),
                                float(rec.get("sum", 0.0)))
        return state, totals

    def _apply(self, rank: int, payload: dict, resyncs: list):
        # caller holds self._lock; True when a FULL snapshot was applied
        # (it answers any resync request: the poll then clears it)
        inc = int(payload.get("incarnation", 0))
        seq = int(payload.get("seq", 0))
        pid = int(payload.get("pid", 0))
        delta = payload.get("delta") or {"full": True, "metrics": {}}
        st = self._ranks.get(rank)
        if st is not None and st.incarnation == inc \
                and pid and st.pid and pid != st.pid:
            # two live processes publishing one (rank, incarnation)
            # stream: a misconfigured fleet (N hand-joined replicas
            # without distinct PADDLE_REPLICA_IDs). Last writer wins
            # below — but the flapping must be OBSERVABLE, never a
            # silent resync storm
            monitor.record_swallowed(
                "fleet.rank_collision",
                RuntimeError(
                    f"rank {rank} incarnation {inc} published by both "
                    f"pid {st.pid} and pid {pid}: give each replica a "
                    f"distinct PADDLE_REPLICA_ID (or rank)"))
        fresh_stream = st is None or st.incarnation != inc
        if not fresh_stream:
            # health is the rank's state now, not a delta: it is never
            # held back by a gap in the metrics stream
            st.health = dict(payload.get("health") or {})
        if fresh_stream and not delta.get("full"):
            # mid-stream join (aggregator restarted, or a relaunched
            # rank whose first full publish we missed): hold the old
            # view and ask for an absolute snapshot
            self._request_resync(rank, st, inc,
                                 payload.get("replica", str(rank)),
                                 resyncs)
            return
        if fresh_stream:
            st = _RankState(incarnation=inc,
                            replica=str(payload.get("replica", rank)))
            self._ranks[rank] = st
        elif seq == st.seq:
            return                     # same payload re-read: idempotent
        elif not delta.get("full") and seq != st.seq + 1:
            self._request_resync(rank, st, inc, st.replica, resyncs)
            return
        metrics.apply_delta(st.metrics, delta)
        st.seq = seq
        st.incarnation = inc
        st.replica = str(payload.get("replica", st.replica))
        st.pid = pid or st.pid
        st.health = dict(payload.get("health") or {})
        st.clock_offset_ns = int(payload.get("clock_offset_ns", 0))
        st.resync_pending = False
        return bool(delta.get("full"))

    def _request_resync(self, rank: int, st: Optional[_RankState],
                        inc: int, replica: str, resyncs: list):
        # caller holds self._lock; the store write itself happens
        # after release (resyncs is the poll round's write list)
        if st is not None and st.resync_pending:
            return
        if st is None:
            st = _RankState(incarnation=inc, replica=str(replica))
            self._ranks[rank] = st
        st.resync_pending = True
        resyncs.append((rank, st))

    def refresh(self, min_interval_s: float = 0.2):
        """Rate-limited poll — what the HTTP handlers call, so a
        scrape hammer (N dashboards) doesn't multiply store traffic.
        Non-blocking: when another thread is already mid-poll this
        returns immediately and the caller serves the current view."""
        if time.monotonic() - self._last_poll < min_interval_s:
            return
        if not self._poll_lock.acquire(blocking=False):
            return
        try:
            self._poll_inner()
        finally:
            self._poll_lock.release()

    # ------------------------------------------------------------- reads
    def fleet_registry(self) -> Dict[str, object]:
        """The merged registry: every rank's series relabeled with
        ``rank=``/``replica=``/``incarnation=``, plus the aggregator's
        meta series (rank census, per-rank up/skew) — feed it to
        ``telemetry_server.prometheus_text``."""
        with self._lock:
            out: Dict[str, object] = {}
            stale = 0
            for rank, st in self._ranks.items():
                extra = {"rank": str(rank), "replica": st.replica,
                         "incarnation": str(st.incarnation)}
                for key, rec in st.metrics.items():
                    out[_merge_labels(key, extra)] = \
                        metrics.state_metric(key, rec)
                up = metrics.Gauge(_merge_labels(
                    "fleet.rank_up",
                    {"rank": str(rank),
                     "incarnation": str(st.incarnation)}))
                up._value = up._peak = 0.0 if st.stale else 1.0
                out[up.name] = up
                skew = metrics.Gauge(_merge_labels(
                    "fleet.clock_skew_ns", {"rank": str(rank)}))
                skew._value = skew._peak = float(st.clock_offset_ns)
                out[skew.name] = skew
                stale += st.stale
            total = metrics.Gauge("fleet.ranks_total")
            total._value = total._peak = float(len(self._ranks))
            out[total.name] = total
            g_stale = metrics.Gauge("fleet.ranks_stale")
            g_stale._value = g_stale._peak = float(stale)
            out[g_stale.name] = g_stale
            return out

    def healthz(self) -> Dict:
        """The ``/fleet/healthz`` rollup: per-replica ready/reason/
        headroom plus the fleet verdict — ready iff every known rank
        is ready, none is stale, and (when the world size is known)
        everyone has reported."""
        straggler_ranks = set(self.straggler.straggler_ranks())
        slo_states = self.slo_evaluator.states()
        with self._lock:
            ranks = {}
            stale = 0
            all_ready = True
            for rank, st in sorted(self._ranks.items()):
                h = st.health or {}
                ready = bool(h.get("ready", False)) and not st.stale
                all_ready = all_ready and ready
                stale += st.stale
                entry = {
                    "ready": ready,
                    "reason": "stale" if st.stale
                    else h.get("reason"),
                    "stale": st.stale,
                    "incarnation": st.incarnation,
                    "replica": st.replica,
                    "age_s": round(st.age_s, 3)
                    if st.age_s is not None else None,
                    # marked, never dropped: a straggler stays ready
                    # (it IS serving/stepping) but the router/operator
                    # sees the flag
                    "straggler": rank in straggler_ranks,
                }
                for k in ("predicted_headroom_bytes",
                          "predicted_peak_bytes", "free_tokens",
                          "capacity_tokens", "queue_depth",
                          "pending_prefill_tokens",
                          "prefill_chunks_queued"):
                    if k in h:
                        entry[k] = h[k]
                ranks[str(rank)] = entry
            seen = len(self._ranks)
            missing = max(self.expected_ranks - seen, 0) \
                if self.expected_ranks else 0
            return {
                "ready": all_ready and stale == 0 and missing == 0
                and seen > 0,
                "ranks_total": seen,
                "ranks_stale": stale,
                "ranks_expected": self.expected_ranks,
                "ranks_missing": missing,
                "stale_after_s": self.stale_after_s,
                "stragglers": sorted(straggler_ranks),
                "slo": slo_states,
                "ranks": ranks,
            }

    def slo_report(self) -> Dict:
        """The fleet section of the telemetry server's ``/slo`` body:
        fleet-scope SLO states + alert history + straggler flags."""
        doc = self.slo_evaluator.report()
        doc["stragglers"] = self.straggler.straggler_ranks()
        doc["straggler_flags"] = self.straggler.flags()
        return doc

    # --------------------------------------------------------- lifecycle
    def start(self) -> "FleetAggregator":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="fleet-aggregate")
        self._thread.start()
        return self

    def _loop(self):
        while True:
            try:
                self.poll()
            except Exception as e:
                monitor.record_swallowed("fleet.aggregate_loop", e)
            if self._stop.wait(self.period_s):
                return

    def stop(self):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.period_s + 5.0)


# ---------------------------------------------------------------- wiring

class FleetMember:
    """One process's fleet-telemetry handles: always a publisher,
    plus the aggregator on the elected rank."""

    def __init__(self, publisher: MetricsPublisher,
                 aggregator: Optional[FleetAggregator]):
        self.publisher = publisher
        self.aggregator = aggregator

    def stop(self):
        self.publisher.stop()
        if self.aggregator is not None:
            self.aggregator.stop()


def start(store, health_fn: Optional[Callable[[], Dict]] = None,
          aggregate: Optional[bool] = None,
          period_s: Optional[float] = None,
          namespace: Optional[str] = None) -> FleetMember:
    """Join the fleet plane: start this rank's publisher (and, on the
    elected rank — rank 0 unless ``aggregate`` overrides — the
    aggregator). Starting the publisher enables the registry: joining
    the fleet pane is opting into recording, the TelemetryServer
    contract."""
    metrics.enable()
    ident = local_identity()
    pub = MetricsPublisher(store, identity=ident, period_s=period_s,
                           health_fn=health_fn,
                           namespace=namespace).start()
    agg = None
    if aggregate is None:
        aggregate = ident.rank == 0
    if aggregate:
        agg = FleetAggregator(store, period_s=period_s,
                              namespace=namespace).start()
    return FleetMember(pub, agg)


def start_from_env(health_fn: Optional[Callable[[], Dict]] = None) \
        -> Optional[FleetMember]:
    """The ``PADDLE_FLEET_STORE=host:port`` opt-in (the launcher's
    ``--fleet_store`` exports it): connect a TCPStore client and join
    the plane. Unset/empty -> None; garbage is swallowed observably
    (a bad knob must not take the replica down)."""
    raw = os.environ.get("PADDLE_FLEET_STORE", "").strip()
    if not raw:
        return None
    host, _, port_s = raw.rpartition(":")
    try:
        port = int(port_s)
        if not host:
            raise ValueError(raw)
    except ValueError:
        monitor.record_swallowed(
            "fleet.store_addr",
            ValueError(f"PADDLE_FLEET_STORE={raw!r}"))
        return None
    from .store import TCPStore
    try:
        store = TCPStore(host, port, timeout=30.0)
        return start(store, health_fn=health_fn)
    except Exception as e:
        monitor.record_swallowed("fleet.store_connect", e)
        return None

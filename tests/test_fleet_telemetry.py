"""Fleet observability plane (ISSUE 15): delta-encoded snapshot
protocol, publisher/aggregator over the TCPStore, /fleet/metrics +
/fleet/healthz live HTTP (the 4-process acceptance gate: kill a rank
-> stale within the deadline, survivors keep scraping clean), the
concurrent-scrape hammer, clock-aligned trace merge, and the 3-process
chaos post-mortem."""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder, metrics, monitor
from paddle_tpu.core.telemetry_server import (TelemetryServer,
                                              prometheus_text)
from paddle_tpu.distributed import fleet_telemetry as ft
from paddle_tpu.distributed.store import TCPStore
from tests.test_telemetry import parse_prometheus

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.disable()
    metrics.reset()
    yield
    metrics.disable()
    metrics.reset()


@pytest.fixture()
def store():
    s = TCPStore("127.0.0.1", 0, is_master=True)
    yield s
    s.shutdown_server()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# ------------------------------------------------------ delta protocol


class TestSnapshotDelta:
    def test_full_then_delta_roundtrip(self):
        metrics.enable()
        metrics.counter("t.c", kind="a").inc(3)
        metrics.gauge("t.g").set(2.5)
        h = metrics.histogram("t.h", bounds=(1.0, 10.0))
        h.observe(0.5)
        state, delta = metrics.snapshot_delta(None)
        assert delta["full"]
        mirror = metrics.apply_delta({}, delta)
        assert mirror["t.c{kind=a}"]["value"] == 3
        assert mirror["t.g"]["value"] == 2.5
        assert mirror["t.h"]["count"] == 1

        metrics.counter("t.c", kind="a").inc(2)
        h.observe(5.0)
        state2, d2 = metrics.snapshot_delta(state)
        assert not d2["full"]
        # unchanged metrics are omitted (the delta-encoding point)
        assert "t.g" not in d2["metrics"]
        assert d2["metrics"]["t.c{kind=a}"] == {"kind": "counter",
                                                "d": 2}
        metrics.apply_delta(mirror, d2)
        assert mirror["t.c{kind=a}"]["value"] == 5
        assert mirror["t.h"]["count"] == 2
        assert mirror["t.h"]["counts"] == \
            metrics._metric_state(h)["counts"]

    def test_reset_rebaselines_absolute(self):
        metrics.enable()
        c = metrics.counter("t.reset")
        c.inc(5)
        state, _ = metrics.snapshot_delta(None)
        c.reset()
        c.inc(1)
        _, delta = metrics.snapshot_delta(state)
        rec = delta["metrics"]["t.reset"]
        assert "d" not in rec and rec["value"] == 1  # absolute re-send
        mirror = metrics.apply_delta(
            {"t.reset": {"kind": "counter", "value": 5}}, delta)
        assert mirror["t.reset"]["value"] == 1

    def test_delta_for_unseen_metric_dropped(self):
        # a delta record arriving without its absolute baseline (missed
        # payload) must not corrupt the state — it is dropped, resync
        # re-sends absolute
        mirror = metrics.apply_delta(
            {}, {"full": False,
                 "metrics": {"t.x": {"kind": "counter", "d": 4}}})
        assert "t.x" not in mirror

    def test_quiet_registry_publishes_empty_delta(self):
        metrics.enable()
        metrics.counter("t.q").inc()
        state, _ = metrics.snapshot_delta(None)
        _, delta = metrics.snapshot_delta(state)
        assert delta == {"full": False, "metrics": {}}


# ----------------------------------------------- publisher + aggregator


class TestPublisherAggregator:
    def test_merge_labels_and_staleness(self, store):
        metrics.enable()
        monitor.record_serve_request("completed")
        monitor.record_serve_ttft(0.01)
        pub = ft.MetricsPublisher(store, period_s=0.2)
        agg = ft.FleetAggregator(store, period_s=0.2,
                                 stale_after_s=0.6, expected_ranks=1)
        pub.publish_now()
        agg.poll()
        reg = agg.fleet_registry()
        key = ("serve.requests{incarnation=0,rank=0,replica=0,"
               "status=completed}")
        assert key in reg and reg[key].value == 1
        # the merged histogram is a real Histogram the renderer accepts
        hkeys = [k for k in reg if k.startswith("serve.ttft{")]
        assert len(hkeys) == 1 and reg[hkeys[0]].count == 1
        assert reg["fleet.ranks_total"].value == 1
        assert reg["fleet.ranks_stale"].value == 0
        roll = agg.healthz()
        assert roll["ready"] and roll["ranks"]["0"]["ready"]
        # second publish is a DELTA; re-polling the same seq twice is
        # idempotent
        monitor.record_serve_request("completed")
        pub.publish_now()
        agg.poll()
        agg.poll()
        reg = agg.fleet_registry()
        assert reg[key].value == 2
        # silence past the deadline -> stale, MARKED not dropped
        time.sleep(0.8)
        agg.poll()
        roll = agg.healthz()
        assert not roll["ready"]
        assert roll["ranks_stale"] == 1
        assert roll["ranks"]["0"]["stale"] and \
            roll["ranks"]["0"]["reason"] == "stale"
        reg = agg.fleet_registry()
        assert reg[key].value == 2           # series survive staleness
        up_key = "fleet.rank_up{incarnation=0,rank=0}"
        assert reg[up_key].value == 0.0
        # ...and a fresh publish revives the rank
        pub.publish_now()
        agg.poll()
        assert agg.healthz()["ranks"]["0"]["stale"] is False

    def test_seq_gap_triggers_resync(self, store):
        metrics.enable()
        c = metrics.counter("t.gap")
        c.inc()
        pub = ft.MetricsPublisher(store, period_s=0.2)
        agg = ft.FleetAggregator(store, period_s=0.2)
        pub.publish_now()
        agg.poll()
        # two publishes between polls: the aggregator misses seq 1
        c.inc()
        pub.publish_now()
        c.inc()
        pub.publish_now()
        agg.poll()      # gap detected -> resync requested, not applied
        key = "t.gap{incarnation=0,rank=0,replica=0}"
        assert agg.fleet_registry()[key].value == 1
        pub.publish_now()   # answers the resync with a FULL snapshot
        agg.poll()
        assert agg.fleet_registry()[key].value == 3

    def test_resync_survives_a_reader_slower_than_the_writer(self, store):
        """The rank publishes twice a poll (a loaded host): the request
        for a full snapshot stays up until the aggregator has read one,
        so the full payload cannot be overwritten unread; and health,
        which is state and no delta, is never held back by the gap."""
        metrics.enable()
        c = metrics.counter("t.slow")
        c.inc()
        health = {"ready": False}
        pub = ft.MetricsPublisher(store, period_s=0.2,
                                  health_fn=lambda: dict(health))
        agg = ft.FleetAggregator(store, period_s=0.2)
        pub.publish_now()
        agg.poll()
        key = "t.slow{incarnation=0,rank=0,replica=0}"
        # rounds of two publishes a poll: a gap (the view holds, a full
        # snapshot is asked for), then both publishes are full and the
        # newest is applied, whichever the reader finds; and so on: the
        # view is never more than one round old
        for total, seen in ((3, 1), (5, 5), (7, 5), (9, 9)):
            health["ready"] = total > 3
            c.inc()
            pub.publish_now()
            c.inc()
            pub.publish_now()
            agg.poll()
            assert agg.healthz()["ranks"]["0"]["ready"] is (total > 3)
            assert agg.fleet_registry()[key].value == seen
        # the request was cleared when answered: deltas again, and a
        # reader that keeps pace follows them
        c.inc()
        assert pub.publish_now()["delta"].get("full") is not True
        agg.poll()
        assert agg.fleet_registry()[key].value == 10

    def test_new_incarnation_replaces_stream(self, store, monkeypatch):
        metrics.enable()
        metrics.counter("t.inc").inc(7)
        ft.MetricsPublisher(store, period_s=0.2).publish_now()
        agg = ft.FleetAggregator(store, period_s=0.2)
        agg.poll()
        # relaunched rank: new incarnation, counters restart
        metrics.reset()
        metrics.counter("t.inc").inc(1)
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "1")
        ft.MetricsPublisher(store, period_s=0.2).publish_now()
        agg.poll()
        reg = agg.fleet_registry()
        assert reg["t.inc{incarnation=1,rank=0,replica=0}"].value == 1
        assert not any("incarnation=0" in k for k in reg)
        assert agg.healthz()["ranks"]["0"]["incarnation"] == 1

    def test_publisher_excludes_fleet_meta_plane(self, store):
        metrics.enable()
        monitor.record_fleet_ranks(3, 1)    # aggregator-side series
        metrics.counter("t.mine").inc()
        payload = ft.MetricsPublisher(store, period_s=0.2).publish_now()
        names = list(payload["delta"]["metrics"])
        assert "t.mine" in names
        assert not any(n.startswith("fleet.") for n in names)

    def test_health_fn_failure_is_not_fatal(self, store):
        metrics.enable()

        def boom():
            raise RuntimeError("injected")

        pub = ft.MetricsPublisher(store, period_s=0.2, health_fn=boom)
        payload = pub.publish_now()
        assert payload["health"]["ready"] is False

    def test_failed_publish_never_loses_a_window(self, store,
                                                 monkeypatch):
        """A store blip mid-publish must not lose that window's
        deltas: the baseline commits only after the payload write
        succeeds, so the retry re-covers the window under the same
        seq."""
        metrics.enable()
        c = metrics.counter("t.blip")
        pub = ft.MetricsPublisher(store, period_s=0.2)
        agg = ft.FleetAggregator(store, period_s=0.2)
        c.inc()
        pub.publish_now()            # seq 0, full, value 1
        agg.poll()
        orig_set = store.set
        armed = {"on": True}

        def flaky_set(key, value):
            if armed["on"] and "/m/" in key:
                armed["on"] = False
                raise RuntimeError("injected store blip")
            return orig_set(key, value)

        monkeypatch.setattr(store, "set", flaky_set)
        c.inc()
        with pytest.raises(RuntimeError, match="injected"):
            pub.publish_now()        # window {+1} NOT committed
        c.inc()
        payload = pub.publish_now()  # retry covers BOTH increments
        assert payload["seq"] == 1
        assert payload["delta"]["metrics"]["t.blip"]["d"] == 2
        agg.poll()
        key = "t.blip{incarnation=0,rank=0,replica=0}"
        assert agg.fleet_registry()[key].value == 3

    def test_rank_collision_is_observable(self, store):
        """Two live processes publishing one (rank, incarnation)
        stream (hand-joined replicas without distinct replica ids):
        never a silent flap — errors.swallowed names the collision."""
        metrics.enable()
        ident_a = ft.FleetIdentity(rank=0, world_size=1, incarnation=0,
                                   replica="0", pid=111)
        ident_b = ft.FleetIdentity(rank=0, world_size=1, incarnation=0,
                                   replica="0", pid=222)
        agg = ft.FleetAggregator(store, period_s=0.2)
        ft.MetricsPublisher(store, identity=ident_a,
                            period_s=0.2).publish_now()
        agg.poll()
        ft.MetricsPublisher(store, identity=ident_b,
                            period_s=0.2).publish_now()
        agg.poll()
        assert metrics.snapshot()[
            "errors.swallowed{where=fleet.rank_collision}"][
            "value"] >= 1

    def test_numeric_replica_id_doubles_as_rank(self, monkeypatch):
        """Hand-joined replicas (no launcher): a numeric
        PADDLE_REPLICA_ID becomes the fleet rank so N replicas never
        clobber one stream."""
        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.setenv("PADDLE_REPLICA_ID", "5")
        ident = ft.local_identity()
        assert ident.rank == 5 and ident.replica == "5"
        monkeypatch.setenv("PADDLE_REPLICA_ID", "pod-a")   # label only
        assert ft.local_identity().rank == 0
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")       # launcher wins
        monkeypatch.setenv("PADDLE_REPLICA_ID", "5")
        assert ft.local_identity().rank == 2

    def test_refresh_never_blocks_behind_a_wedged_poll(self, store):
        """A store outage mid-poll must not wedge the scrape path:
        refresh() skips when another thread holds the poll round, and
        the view lock is never held across store I/O."""
        metrics.enable()
        ft.MetricsPublisher(store, period_s=0.2).publish_now()
        agg = ft.FleetAggregator(store, period_s=0.2)
        agg.poll()
        agg._last_poll = float("-inf")    # due for a refresh
        with agg._poll_lock:              # a poll round is "in flight"
            t0 = time.monotonic()
            agg.refresh()                 # returns immediately
            assert time.monotonic() - t0 < 0.5
            assert agg._last_poll == float("-inf")
            # the merged view stays readable while the poll is wedged
            assert agg.fleet_registry()["fleet.ranks_total"].value == 1
            assert agg.healthz()["ranks_total"] == 1

    def test_clock_handshake_records_offset(self, store):
        metrics.enable()
        pub = ft.MetricsPublisher(store, period_s=0.2)
        offset, rtt = pub.sync_clock()
        # same process as the store server: offset is sub-second, rtt
        # positive; the dump metadata carries the same number
        assert abs(offset) < 1e9 and rtt > 0
        assert flight_recorder.clock_offset_ns() == offset
        kinds = [k for _, k, _ in flight_recorder.events()]
        assert "fleet.clock_sync" in kinds


# ------------------------------------------------------ /fleet endpoints


class TestFleetEndpoints:
    def test_fleet_endpoints_404_without_aggregator(self):
        server = TelemetryServer(port=0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            for path in ("/fleet/metrics", "/fleet/healthz"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    _get(base + path)
                assert e.value.code == 404
        finally:
            server.stop()

    def test_fleet_metrics_and_healthz_over_http(self, store):
        metrics.enable()
        monitor.record_serve_request("completed")
        pub = ft.MetricsPublisher(store, period_s=0.2)
        pub.publish_now()
        agg = ft.FleetAggregator(store, period_s=0.2,
                                 stale_after_s=5.0, expected_ranks=1)
        server = TelemetryServer(port=0).start().attach_aggregator(agg)
        try:
            base = f"http://127.0.0.1:{server.port}"
            code, text = _get(base + "/fleet/metrics")
            assert code == 200
            parsed = parse_prometheus(text)
            assert parsed["samples"][
                ("serve_requests",
                 frozenset({("rank", "0"), ("replica", "0"),
                            ("incarnation", "0"),
                            ("status", "completed")}))] == 1
            assert parsed["samples"][("fleet_ranks_total",
                                      frozenset())] == 1
            # scrape hygiene rides on the fleet render too
            assert ("process_uptime_seconds", frozenset()) in \
                parsed["samples"]
            code, body = _get(base + "/fleet/healthz")
            roll = json.loads(body)
            assert code == 200 and roll["ready"] and \
                roll["ranks"]["0"]["ready"]
        finally:
            server.stop()


# ------------------------------------------- concurrent-scrape hammer


class TestScrapeHammer:
    def test_four_threads_against_mutating_registry(self, store):
        """Satellite: 4 threads hammering /metrics + /fleet/metrics
        while the registry and the aggregator mutate underneath — no
        exception, every render parseable, histogram cumulatives
        monotone."""
        metrics.enable()
        pub = ft.MetricsPublisher(store, period_s=0.05)
        agg = ft.FleetAggregator(store, period_s=0.05,
                                 stale_after_s=5.0)
        server = TelemetryServer(port=0).start().attach_aggregator(agg)
        pub.start()
        agg.start()
        stop = threading.Event()
        errors = []

        def mutate():
            i = 0
            while not stop.is_set():
                monitor.record_serve_request("completed")
                monitor.record_serve_ttft(0.001 * (1 + i % 50))
                monitor.record_serve_queue_depth(i % 7)
                i += 1
                time.sleep(0.0005)

        def scrape():
            base = f"http://127.0.0.1:{server.port}"
            try:
                for n in range(12):
                    for path in ("/metrics", "/fleet/metrics"):
                        code, text = _get(base + path)
                        assert code == 200
                        parsed = parse_prometheus(text)
                        buckets = sorted(
                            ((dict(k[1]).get("le"), v)
                             for k, v in parsed["samples"].items()
                             if k[0] == "serve_ttft_bucket"
                             and dict(k[1]).get("rank", "0") == "0"),
                            key=lambda kv: float("inf")
                            if kv[0] == "+Inf" else float(kv[0]))
                        vals = [v for _, v in buckets]
                        assert vals == sorted(vals), \
                            f"non-monotone cumulatives on {path}"
            except Exception as e:  # surfaced on the main thread
                errors.append(e)

        mut = threading.Thread(target=mutate, daemon=True)
        mut.start()
        scrapers = [threading.Thread(target=scrape, daemon=True)
                    for _ in range(4)]
        try:
            for t in scrapers:
                t.start()
            for t in scrapers:
                t.join(timeout=60)
                assert not t.is_alive(), "scraper wedged"
        finally:
            stop.set()
            mut.join(timeout=5)
            pub.stop(final_publish=False)
            agg.stop()
            server.stop()
        assert not errors, errors


# ------------------------------------------------ engine fleet wiring


class TestEngineFleetWiring:
    def test_engine_joins_fleet_from_env(self, store, monkeypatch):
        """PADDLE_FLEET_STORE on a ServingEngine: the replica
        publishes its health + serve.* series, and (as rank 0) its
        telemetry server grows the /fleet/* endpoints."""
        from paddle_tpu.inference import Config
        from paddle_tpu.models.gpt import gpt
        from paddle_tpu.serving import ServingEngine
        monkeypatch.setenv("PADDLE_FLEET_STORE",
                           f"127.0.0.1:{store.port}")
        monkeypatch.setenv("PADDLE_JOB_ID", "engwire")
        monkeypatch.setenv("PADDLE_FLEET_METRICS_PERIOD_S", "0.2")
        paddle.seed(0)
        m = gpt("test-tiny")
        m.eval()
        spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
        cfg = (Config().from_layer(m, spec)
               .enable_generation(max_new_tokens=2,
                                  prefill_buckets=(16,), max_batch=1)
               .enable_serving(telemetry_port=0))
        eng = ServingEngine(cfg, poll_every=1)
        try:
            assert eng.fleet is not None
            assert eng.fleet.aggregator is not None   # rank 0 elected
            assert eng.telemetry.aggregator is eng.fleet.aggregator
            eng.submit(np.arange(1, 5, dtype=np.int32)).result(
                timeout=60)
            eng.fleet.publisher.publish_now()
            base = f"http://127.0.0.1:{eng.telemetry.port}"

            # the plane is eventually consistent: the constructor-time
            # publishes predate warmup (ready=False), and a seq gap
            # between the background publisher and aggregator threads
            # resolves via resync within a period or two — retry
            def rank0_ready():
                roll = json.loads(_get(base + "/fleet/healthz")[1])
                return roll["ranks"]["0"]["ready"]

            _wait_until(rank0_ready, 30,
                        "rank 0 ready in /fleet/healthz")
            roll = json.loads(_get(base + "/fleet/healthz")[1])
            assert "queue_depth" in roll["ranks"]["0"]

            key = ("serve_requests",
                   frozenset({("rank", "0"), ("replica", "0"),
                              ("incarnation", "0"),
                              ("status", "completed")}))

            def completed_visible():
                parsed = parse_prometheus(
                    _get(base + "/fleet/metrics")[1])
                return parsed["samples"].get(key, 0) >= 1

            _wait_until(completed_visible, 15,
                        "completed request in /fleet/metrics")
        finally:
            eng.shutdown()
        assert eng.fleet is None


# ----------------------------------------------------- 4-process e2e


_WORKER = """\
import os, sys, time
from paddle_tpu.core import metrics
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed import fleet_telemetry as ft

host, port = sys.argv[1], int(sys.argv[2])
store = TCPStore(host, port, timeout=30.0)
member = ft.start(store, aggregate=False, period_s=0.25)
while True:
    metrics.counter("gen.tokens").inc(1)
    time.sleep(0.05)
"""


def _spawn_worker(script, store_port, rank, world, extra_env=None,
                  args=()):
    env = dict(os.environ)
    env.update({"PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO_ROOT +
                os.pathsep + env.get("PYTHONPATH", "")})
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, script, "127.0.0.1", str(store_port), *args],
        env=env, cwd=REPO_ROOT)


def _wait_until(cond, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


class TestFleetE2E:
    def test_four_process_job_one_pane_kill_one_rank(
            self, store, tmp_path, monkeypatch):
        """THE acceptance gate: a 4-process TCPStore job serves ONE
        /fleet/metrics with per-rank labeled series and a
        /fleet/healthz rollup over live HTTP; killing a rank flips it
        stale within the publish deadline while the remaining ranks
        keep scraping clean. Zero jax cross-process collectives."""
        monkeypatch.setenv("PADDLE_JOB_ID", "e2e4")
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        period, stale_after = 0.25, 1.0
        agg = ft.FleetAggregator(store, period_s=period,
                                 stale_after_s=stale_after,
                                 expected_ranks=4,
                                 namespace="__fleet/e2e4").start()
        server = TelemetryServer(port=0).start().attach_aggregator(agg)
        base = f"http://127.0.0.1:{server.port}"
        procs = [_spawn_worker(str(script), store.port, r, 4,
                               extra_env={"PADDLE_JOB_ID": "e2e4"})
                 for r in range(4)]
        try:
            def roll():
                return json.loads(_get(base + "/fleet/healthz")[1])

            _wait_until(
                lambda: roll()["ranks_total"] == 4
                and roll()["ranks_stale"] == 0, 30,
                "all 4 ranks publishing")
            assert roll()["ready"]

            code, text = _get(base + "/fleet/metrics")
            assert code == 200
            parsed = parse_prometheus(text)

            def tokens(snapshot, rank):
                return snapshot["samples"].get(
                    ("gen_tokens",
                     frozenset({("rank", str(rank)),
                                ("replica", str(rank)),
                                ("incarnation", "0")})), 0)

            for r in range(4):
                assert tokens(parsed, r) >= 1, f"rank {r} series missing"

            # SIGKILL rank 2: no graceful anything — the hard case
            procs[2].kill()
            procs[2].wait(timeout=10)
            t_kill = time.monotonic()
            _wait_until(lambda: roll()["ranks"]["2"]["stale"],
                        stale_after + 4 * period + 5.0,
                        "killed rank marked stale")
            flip_s = time.monotonic() - t_kill
            r = roll()
            assert not r["ready"] and r["ranks_stale"] == 1
            # survivors untouched — and still scraping clean
            assert not any(r["ranks"][str(k)]["stale"]
                           for k in (0, 1, 3))
            code, text2 = _get(base + "/fleet/metrics")
            assert code == 200
            parsed2 = parse_prometheus(text2)
            # the dead rank's series are STILL THERE (marked, not
            # dropped) and the survivors' counters kept advancing
            assert tokens(parsed2, 2) >= tokens(parsed, 2) > 0
            assert parsed2["samples"][
                ("fleet_rank_up",
                 frozenset({("rank", "2"), ("incarnation", "0")}))] == 0
            assert any(tokens(parsed2, k) > tokens(parsed, k)
                       for k in (0, 1, 3))
            # the flip honored the deadline (generous slack for a
            # loaded CI box: deadline + a few aggregation periods)
            assert flip_s < stale_after + 4 * period + 5.0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            agg.stop()
            server.stop()


# --------------------------------------------------------- trace merge


class TestTraceMerge:
    @staticmethod
    def _dump(rank, pid, offset_ns, events, restart=0):
        """Synthetic dump: anchor_perf=0 so ts µs IS local wall ns/1000
        above the 1s epoch."""
        anchor_wall = 1_000_000_000
        te = []
        for name, master_ns, args in events:
            local_wall = master_ns + offset_ns      # skewed local clock
            te.append({"name": name, "ph": "i", "s": "p",
                       "cat": "flight",
                       "ts": (local_wall - anchor_wall) / 1000.0
                       + anchor_wall / 1000.0,
                       "pid": pid, "tid": 0, "args": args})
        return {"traceEvents": te,
                "metadata": {"rank": rank, "restart_count": restart,
                             "pid": pid, "clock_offset_ns": offset_ns,
                             "anchor_wall_ns": anchor_wall,
                             "anchor_perf_ns": anchor_wall,
                             "reason": "test",
                             "dropped_events": 0}}

    def test_offset_adjustment_fixes_cross_rank_ordering(self):
        from tools.trace_merge import merge
        s = 1_000_000_000     # events sit 1s past the epoch anchor
        # victim (rank 1): clock runs 50ms AHEAD of the master; its
        # SIGTERM lands at master t=100ms. Peer (rank 0, clock true)
        # detects at master t=110ms. On RAW local clocks the victim's
        # event looks LATER (150ms vs 110ms) — the inversion the
        # offset adjustment must fix.
        victim = self._dump(1, 111, 50_000_000,
                            [("resilience.preemption",
                              s + 100_000_000, {"source": "signal"})])
        peer = self._dump(0, 222, 0,
                          [("resilience.preemption",
                            s + 110_000_000, {"source": "store"})])
        raw = {e["args"]["source"]: e["ts"]
               for e in victim["traceEvents"] + peer["traceEvents"]}
        assert raw["signal"] > raw["store"]          # inverted raw
        merged = merge([victim, peer])
        assert merged["metadata"]["clock_aligned"]
        ts = {e["args"]["source"]: e["ts"]
              for e in merged["traceEvents"] if e.get("ph") == "i"}
        assert ts["signal"] < ts["store"]            # fixed
        assert ts["store"] - ts["signal"] == pytest.approx(10_000.0)
        names = {e["args"]["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names == {"rank0.0 (pid 222, test)",
                         "rank1.0 (pid 111, test)"}

    def test_real_dumps_round_trip(self, tmp_path, monkeypatch):
        """Two live recorder dumps (different env identities +
        offsets) merge into one valid trace with one track each, and
        the filenames embed (rank, restart, pid)."""
        from tools.trace_merge import merge_paths
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        for rank, offset in ((0, 0), (1, 25_000_000)):
            monkeypatch.setenv("PADDLE_TRAINER_ID", str(rank))
            monkeypatch.setenv("PADDLE_RESTART_COUNT", "0")
            flight_recorder.configure(capacity=64, on=True)
            flight_recorder.set_clock_offset_ns(offset)
            flight_recorder.record("checkpoint.commit", step=rank)
            path = flight_recorder.dump(reason="postmortem")
            name = os.path.basename(path)
            assert name.startswith(
                f"flightrecorder_postmortem_r{rank}i0_p{os.getpid()}")
        flight_recorder.set_clock_offset_ns(0)
        # the process's ring again: a ring of 64 left behind cuts the
        # next file's serving runs short
        flight_recorder.configure(
            capacity=flight_recorder.DEFAULT_CAPACITY, on=True)
        merged = merge_paths([str(tmp_path)])
        assert set(merged["metadata"]["merged_tracks"]) == \
            {"rank0.0", "rank1.0"}
        assert merged["metadata"]["clock_aligned"]
        instants = [e for e in merged["traceEvents"]
                    if e.get("ph") == "i"
                    and e["name"] == "checkpoint.commit"]
        assert len(instants) == 2
        for e in merged["traceEvents"]:
            assert "name" in e and "ph" in e and "pid" in e

    def test_duplicate_track_from_two_jobs_rejected(self):
        from tools.trace_merge import merge
        a = self._dump(0, 111, 0, [("checkpoint.commit",
                                    1_100_000_000, {})])
        b = self._dump(0, 222, 0, [("checkpoint.commit",
                                    1_100_000_000, {})])
        with pytest.raises(ValueError, match="two different jobs"):
            merge([a, b])

    def test_two_dumps_of_one_process_dedupe_ring_overlap(self):
        """One process can dump twice (preemption auto-dump, then a
        later manual/crash dump): the shared ring prefix renders ONCE
        on the track, the later dump's new events still merge."""
        from tools.trace_merge import merge
        s = 1_000_000_000
        first = self._dump(0, 111, 0,
                           [("resilience.preemption",
                             s + 100_000_000, {"source": "signal"})])
        first["metadata"]["reason"] = "preemption"
        second = self._dump(0, 111, 0,
                            [("resilience.preemption",
                              s + 100_000_000, {"source": "signal"}),
                             ("checkpoint.commit",
                              s + 200_000_000, {"step": 7})])
        second["metadata"]["reason"] = "manual"
        merged = merge([first, second])
        instants = [e for e in merged["traceEvents"]
                    if e.get("ph") == "i"]
        assert len(instants) == 2      # overlap deduped, new event kept
        assert {e["name"] for e in instants} == \
            {"resilience.preemption", "checkpoint.commit"}
        track = merged["metadata"]["merged_tracks"]["rank0.0"]
        assert track["events"] == 2
        assert track["reason"] == "preemption+manual"


# ----------------------------------------------- chaos: fleet post-mortem


_CHAOS_WORKER = """\
import os, sys, time
from paddle_tpu.core import flight_recorder, goodput
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed import fleet_telemetry as ft
from paddle_tpu.distributed.resilience import GracefulShutdown
from paddle_tpu.utils.fault_injection import KillAfter

host, port = sys.argv[1], int(sys.argv[2])
rank = int(os.environ["PADDLE_TRAINER_ID"])
victim = rank == 1
store = TCPStore(host, port, timeout=30.0)
member = ft.start(store, aggregate=False, period_s=0.2)
member.publisher.sync_clock()
killer = KillAfter(6) if victim else None
ledger = goodput.GoodputLedger("train")
with GracefulShutdown(store=store, exit_on_save=victim,
                      store_poll_interval=0.05) as gs:
    with ledger:
        for step in range(500):
            time.sleep(0.02)                     # the "work"
            ledger.charge("compute", 0.02)
            if killer is not None:
                killer.step()
            if gs.check(step):   # victim exits inside check();
                #                  survivors detect via the store flag
                with ledger.timed("preemption_recovery"):
                    time.sleep(0.1)              # elastic re-rendezvous
                break
    snap = ledger.snapshot()
# only survivors reach here
store.set("__result/%d" % rank, snap)
time.sleep(4.0)    # stay live (publishing) while the test asserts
member.stop()
"""


@pytest.mark.chaos
class TestFleetPostMortem:
    def test_three_process_kill_one_post_mortem(self, store, tmp_path,
                                                monkeypatch):
        """Satellite chaos gate: 3-process TCPStore job, SIGTERM kills
        rank 1 mid-run (KillAfter). Assert (a) the aggregator marks
        the victim stale while the survivors stay live (never
        dropped), (b) the merged trace carries the victim's preemption
        event (source=signal) ordered before the peers' detection
        events (source=store), (c) the survivors' recovery wall time
        landed in their preemption_recovery goodput bucket, with the
        ledger invariant holding."""
        monkeypatch.setenv("PADDLE_JOB_ID", "chaos3")
        dump_dir = tmp_path / "dumps"
        dump_dir.mkdir()
        script = tmp_path / "worker.py"
        script.write_text(_CHAOS_WORKER)
        agg = ft.FleetAggregator(store, period_s=0.2,
                                 stale_after_s=0.8, expected_ranks=3,
                                 namespace="__fleet/chaos3").start()
        procs = [_spawn_worker(
            str(script), store.port, r, 3,
            extra_env={"PADDLE_JOB_ID": "chaos3",
                       "PADDLE_FLIGHT_RECORDER_DIR": str(dump_dir)})
            for r in range(3)]
        try:
            # victim exits with the elastic code once check() ran its
            # emergency path
            assert procs[1].wait(timeout=60) == 101
            _wait_until(
                lambda: (lambda h: h["ranks_total"] == 3
                         and h["ranks"]["1"]["stale"]
                         and not h["ranks"]["0"]["stale"]
                         and not h["ranks"]["2"]["stale"])(
                    (agg.poll(), agg.healthz())[1]),
                15, "victim stale beside live survivors")
            # stale is MARKED, not dropped: the victim's series remain
            reg = agg.fleet_registry()
            assert any("rank=1" in k for k in reg)
            for p in (procs[0], procs[2]):
                assert p.wait(timeout=60) == 0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)
            agg.stop()

        # ---- (b) the merged post-mortem: every rank auto-dumped on
        # preemption; one clock-aligned timeline orders the SIGTERM
        # before the detections
        from tools.trace_merge import merge_paths
        merged = merge_paths([str(dump_dir)])
        assert merged["metadata"]["clock_aligned"]
        tracks = merged["metadata"]["merged_tracks"]
        assert set(tracks) == {"rank0.0", "rank1.0", "rank2.0"}
        pre = [(e["pid"], e["ts"], e["args"]["source"])
               for e in merged["traceEvents"]
               if e.get("name") == "resilience.preemption"]
        by_source = {}
        for _, ts, source in pre:
            by_source.setdefault(source, []).append(ts)
        assert len(by_source["signal"]) == 1      # the victim
        assert len(by_source["store"]) == 2       # both peers detected
        assert by_source["signal"][0] < min(by_source["store"])
        # the victim's dump was the preemption auto-dump, identity in
        # the filename
        victim_dumps = [f for f in os.listdir(dump_dir)
                        if f.startswith("flightrecorder_preemption_r1i0")]
        assert victim_dumps, os.listdir(dump_dir)

        # ---- (c) survivors' goodput: recovery landed in its bucket,
        # buckets sum to wall
        for r in (0, 2):
            snap = store.get(f"__result/{r}", timeout=5.0)
            buckets = snap["buckets"]
            assert buckets["preemption_recovery"] >= 0.09, snap
            assert sum(buckets.values()) == \
                pytest.approx(snap["wall_s"], rel=0.05)

"""Launcher / store / spawn / elastic / rpc tests.

Mirrors the reference's pattern of proving distributed plumbing with
single-host multi-process runs (SURVEY.md §4: TestDistBase
test_dist_base.py:901 subprocess workers + env contract assertions).
"""
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from paddle_tpu.distributed.store import TCPStore, free_port
from paddle_tpu.distributed import elastic as el

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ store
def test_tcp_store_set_get_add():
    store = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        store.set("k", {"a": 1})
        assert store.get("k") == {"a": 1}
        assert store.add("n", 2) == 2
        assert store.add("n", 3) == 5
        assert store.delete("k") is True
        assert store.delete("k") is False
        with pytest.raises(TimeoutError):
            store.get("missing", timeout=0.2)
    finally:
        store.shutdown_server()


def test_tcp_store_multiclient_wait_and_barrier():
    master = TCPStore("127.0.0.1", 0, is_master=True)
    port = master.port
    results = []

    def client(i):
        c = TCPStore("127.0.0.1", port)
        c.barrier("b1", 3, timeout=10.0)
        results.append(i)
        c.close()

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        assert results == []  # barrier holds until the 3rd participant
        master.barrier("b1", 3, timeout=10.0)
        for t in threads:
            t.join(10.0)
        assert sorted(results) == [0, 1]
    finally:
        master.shutdown_server()


# ------------------------------------------------------------------ launch
def test_launch_cli_env_contract(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        out = os.environ["OUT_DIR"]
        rank = os.environ["PADDLE_TRAINER_ID"]
        info = {k: os.environ.get(k) for k in
                ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                 "PADDLE_MASTER", "PADDLE_LOCAL_RANK", "PADDLE_JOB_ID")}
        info["argv"] = sys.argv[1:]
        with open(os.path.join(out, f"rank{rank}.json"), "w") as f:
            json.dump(info, f)
    """))
    env = dict(os.environ, OUT_DIR=str(tmp_path), PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--job_id", "jtest",
         "--log_dir", str(tmp_path / "logs"),
         str(script), "--foo", "bar"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    import json
    infos = [json.load(open(tmp_path / f"rank{i}.json"))
             for i in range(2)]
    assert [i["PADDLE_TRAINER_ID"] for i in infos] == ["0", "1"]
    assert all(i["PADDLE_TRAINERS_NUM"] == "2" for i in infos)
    assert all(i["PADDLE_JOB_ID"] == "jtest" for i in infos)
    assert all(i["argv"] == ["--foo", "bar"] for i in infos)
    assert (tmp_path / "logs" / "workerlog.0").exists()


def test_launch_cli_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 3


_CHAOS_WORKER = """
import json
import os
import signal
import sys

sys.path.insert(0, os.environ["REPO"])
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=1")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.auto_checkpoint import (ExeTrainStatus,
                                                    train_epoch_range)

rank = os.environ.get("PADDLE_TRAINER_ID", "0")
KILL_EPOCH = int(os.environ.get("KILL_EPOCH", "-1")) \
    if rank == os.environ.get("KILL_RANK", "0") else -1
marker = os.environ.get("KILL_MARKER", "")

paddle.seed(0)
net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
opt = optimizer.SGD(learning_rate=0.05, parameters=net.parameters())
rng = np.random.RandomState(0)
x = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
y = paddle.to_tensor(rng.randn(16, 1).astype(np.float32))

os.environ["PADDLE_JOB_ID"] = os.environ["PADDLE_JOB_ID"] + "_r" + rank
status = ExeTrainStatus()
final = None
for epoch in train_epoch_range(6, status=status):
    if status.state.get("weights") is not None:
        # restored leaves arrive as framework Tensors
        net.set_state_dict(dict(status.state["weights"]))
        status.state["weights"] = None  # restore once per incarnation
    out = net(x)
    loss = ((out - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    final = float(np.asarray(loss.data))
    if epoch == KILL_EPOCH and marker and not os.path.exists(marker):
        open(marker, "w").close()
        os.kill(os.getpid(), signal.SIGKILL)   # hard preemption
    status.update(weights={k: np.asarray(v.data)
                           for k, v in net.state_dict().items()},
                  loss=final)

if final is None:
    # a relaunched incarnation can resume PAST the last epoch (this
    # rank had already completed every epoch before the pod teardown
    # got to it — a real scheduling race under load): the loop yields
    # nothing, and the honest result is the checkpointed final loss
    final = status.state.get("loss")

with open(os.environ["RESULT_JSON"] + "." + rank, "w") as f:
    json.dump({"loss": final}, f)
"""


@pytest.mark.slow  # ~20s multi-process relaunch e2e on CPU: tier-2
def test_preemption_chaos_resume_parity(tmp_path):
    """VERDICT r3 Next #6: SIGKILL a worker mid-epoch (a real kill,
    not exit-101 cooperation), let the launcher's fault-elastic path
    relaunch it, resume from the auto checkpoint, and land on the SAME
    final loss as an uninterrupted run."""
    script = tmp_path / "chaos_worker.py"
    script.write_text(textwrap.dedent(_CHAOS_WORKER))

    def run(job, kill_epoch, extra_args):
        env = dict(os.environ, REPO=REPO, PYTHONPATH=REPO,
                   PADDLE_RUNNING_ENV="PADDLE_EDL_AUTO_CHECKPOINT",
                   PADDLE_EDL_HDFS_CHECKPOINT_PATH=str(tmp_path / job),
                   KILL_EPOCH=str(kill_epoch), KILL_RANK="0",
                   KILL_MARKER=str(tmp_path / f"{job}.killed"),
                   RESULT_JSON=str(tmp_path / f"{job}.json"))
        env["PADDLE_JOB_ID"] = job
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--job_id", job, *extra_args,
             str(script)],
            env=env, capture_output=True, text=True, timeout=300)
        return r

    # uninterrupted reference run (2-worker pod)
    r0 = run("plain", -1, [])
    assert r0.returncode == 0, r0.stderr
    import json
    ref = [json.load(open(str(tmp_path / "plain.json") + f".{i}"))
           ["loss"] for i in range(2)]

    # chaos run: SIGKILL rank 0 mid-epoch-2; the controller tears the
    # POD down (rank 1 dies with it, possibly mid-epoch too),
    # fault-elastic relaunches everyone, each rank resumes from its
    # own auto checkpoint
    r1 = run("chaos", 2, ["--max_restarts", "2",
                          "--elastic_on_failure"])
    assert r1.returncode == 0, r1.stderr
    assert (tmp_path / "chaos.killed").exists(), \
        "the kill never happened — the chaos leg tested nothing"
    # interrupted epochs were never snapshotted: the restart redoes
    # them from the last completed state, so BOTH ranks' trajectories
    # are identical to the uninterrupted run
    for i in range(2):
        chaos = json.load(open(str(tmp_path / "chaos.json")
                               + f".{i}"))["loss"]
        assert abs(chaos - ref[i]) < 1e-6, (i, chaos, ref[i])

    # without elastic_on_failure a signal death still propagates
    r2 = run("nofault", 2, ["--max_restarts", "2"])
    assert r2.returncode != 0


def test_launch_elastic_restart(tmp_path):
    # worker exits 101 (elastic restart) once, then succeeds
    script = tmp_path / "elastic_worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        marker = os.environ["MARKER"] + os.environ["PADDLE_TRAINER_ID"]
        if not os.path.exists(marker):
            open(marker, "w").close()
            sys.exit(101)
        sys.exit(0)
    """))
    env = dict(os.environ, PYTHONPATH=REPO,
               MARKER=str(tmp_path / "m"))
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restarts", "1", str(script)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------------------ spawn
def _spawn_target(out_dir):
    import json
    import os
    rank = os.environ["PADDLE_TRAINER_ID"]
    with open(os.path.join(out_dir, f"spawn{rank}.json"), "w") as f:
        json.dump({"rank": rank,
                   "world": os.environ["PADDLE_TRAINERS_NUM"]}, f)


def test_spawn(tmp_path):
    from paddle_tpu.distributed.spawn import spawn
    spawn(_spawn_target, args=(str(tmp_path),), nprocs=2)
    import json
    infos = [json.load(open(tmp_path / f"spawn{i}.json"))
             for i in range(2)]
    assert sorted(i["rank"] for i in infos) == ["0", "1"]
    assert all(i["world"] == "2" for i in infos)


def _spawn_fail(_):
    raise ValueError("boom")


def test_spawn_raises_on_child_failure(tmp_path):
    from paddle_tpu.distributed.spawn import spawn
    with pytest.raises(RuntimeError, match="boom"):
        spawn(_spawn_fail, args=(str(tmp_path),), nprocs=1)


def _skip_if_no_multiprocess_cpu(r):
    """Some jaxlib builds ship a CPU client without cross-process
    collectives ("Multiprocess computations aren't implemented on the
    CPU backend") — a toolchain capability gap, not a launcher bug."""
    if "Multiprocess computations aren't implemented" in (r.stderr or ""):
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")


def test_launch_multiprocess_jax_distributed(tmp_path):
    """Two real processes rendezvous via jax.distributed (the TCPStore
    analog) and run a cross-process allgather — the reference's
    test_dist_base subprocess-cluster pattern on the TPU stack."""
    script = tmp_path / "jd_worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {REPO!r})
        import paddle_tpu.distributed as dist
        env = dist.init_parallel_env()
        import jax, jax.numpy as jnp
        assert jax.process_count() == 2
        from jax.experimental import multihost_utils
        got = multihost_utils.process_allgather(
            jnp.array([jax.process_index()]))
        assert sorted(int(x) for x in got.ravel()) == [0, 1]
    """))
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    _skip_if_no_multiprocess_cpu(r)
    assert r.returncode == 0, r.stderr


def test_launch_multihost_global_mesh(tmp_path):
    """2 processes x 4 virtual devices = one 8-device GLOBAL mesh:
    multi-host SPMD with cross-process psum — the multi-pod execution
    model (each host drives its slice-local chips, XLA routes the
    collective) proven on CPU."""
    script = tmp_path / "mesh_worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {REPO!r})
        import paddle_tpu.distributed as dist
        dist.init_parallel_env()
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert jax.process_count() == 2
        assert jax.device_count() == 8  # global
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        # each process contributes its local shard; psum crosses hosts
        local = jnp.arange(4.0) + 4.0 * jax.process_index()

        def summed(x):
            return jax.lax.psum(x, "dp")

        from jax.experimental import multihost_utils
        global_x = multihost_utils.host_local_array_to_global_array(
            local, mesh, P("dp"))
        from jax import shard_map
        out = jax.jit(shard_map(summed, mesh=mesh, in_specs=P("dp"),
                                out_specs=P()))(global_x)
        # fully replicated result: every host reads its local replica
        total = float(np.asarray(out.addressable_data(0)).ravel()[0])
        assert total == sum(range(8)), total
    """))
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        env=env, capture_output=True, text=True, timeout=300)
    _skip_if_no_multiprocess_cpu(r)
    assert r.returncode == 0, r.stderr[-3000:]


# ----------------------------------------------------------------- elastic
def test_elastic_membership_and_scale_event():
    store = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        m1 = el.ElasticManager(store, "job1", (1, 4), host="h1",
                               heartbeat_timeout=30.0)
        m2 = el.ElasticManager(store, "job1", (1, 4), host="h2",
                               heartbeat_timeout=30.0)
        m1.register()
        assert m1.hosts() == ["h1"]
        events = []
        w = threading.Thread(
            target=m1.watch,
            kwargs=dict(on_scale=events.append, poll=0.05, max_events=1),
            daemon=True)
        w.start()
        time.sleep(0.15)
        m2.register()  # scale-up event
        w.join(10.0)
        assert events and events[0] == ["h1", "h2"]
        m2.deregister()
        assert m1.hosts() == ["h1"]
    finally:
        store.shutdown_server()


def test_elastic_watch_dip_below_min_then_rejoin_fires_once():
    """Scale-event semantics: the alive set dipping below min_np fires
    NOTHING (not a viable mesh), and the same host rejoining fires
    EXACTLY one event once the set is viable again."""
    store = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        m1 = el.ElasticManager(store, "job2", (2, 3), host="h1",
                               heartbeat_timeout=30.0)
        m2 = el.ElasticManager(store, "job2", (2, 3), host="h2",
                               heartbeat_timeout=30.0)
        m1.register()
        m2.register()
        events = []
        w = threading.Thread(
            target=m1.watch,
            kwargs=dict(on_scale=events.append, poll=0.05, max_events=1),
            daemon=True)
        w.start()
        time.sleep(0.2)
        assert events == []  # steady viable membership: no event
        m2.deregister()      # dip to 1 < min_np=2: tracked, not fired
        time.sleep(0.3)
        assert events == []
        m2.register()        # rejoin: viable again -> exactly one event
        w.join(10.0)
        assert not w.is_alive()
        assert events == [["h1", "h2"]]
    finally:
        store.shutdown_server()


def test_elastic_deregister_logs_swallowed_store_error():
    """deregister on a dead store must not raise — and must not be
    silent either: the swallowed exception is counted via the monitor."""
    from paddle_tpu.profiler import metrics
    store = TCPStore("127.0.0.1", 0, is_master=True)
    m = el.ElasticManager(store, "job3", (1, 2), host="h1",
                          heartbeat_timeout=30.0)
    m.register()
    store.shutdown_server()
    dead = TCPStore("127.0.0.1", store.port, timeout=0.3)
    m.store = dead
    was = metrics.is_enabled()
    metrics.enable()
    try:
        m.deregister()  # store is gone: swallowed, logged, counted
        snap = metrics.snapshot()
        key = [k for k in snap
               if k.startswith("errors.swallowed") and "elastic" in k]
        assert key, list(snap)[:20]
    finally:
        if not was:
            metrics.disable()
        dead.close()


# --------------------------------------------------------------------- rpc
def _double(x):
    return 2 * x


def test_rpc_single_worker_roundtrip():
    from paddle_tpu.distributed import rpc
    port = free_port()
    store = TCPStore("127.0.0.1", port, is_master=True)
    try:
        rpc.init_rpc("w0", rank=0, world_size=1, store=store)
        assert rpc.rpc_sync("w0", _double, args=(21,)) == 42
        fut = rpc.rpc_async("w0", _double, args=(5,))
        assert fut.result(timeout=10) == 10
        info = rpc.get_worker_info()
        assert info.name == "w0" and info.rank == 0
        rpc.shutdown()
    finally:
        store.shutdown_server()

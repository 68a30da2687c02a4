"""Executable-persistence tests (ISSUE 9): the jit.compile_cache layer.

Covers: store roundtrip + the jit.compile_cache.* metrics family, THE
tier-1 warm-restart gate (a rebuilt ServingEngine in a cleared-jax-cache
state loads every program from the store — hits == program count,
misses == 0, zero XLA compiles — with outputs bitwise-equal to the cold
reference), the Predictor's per-bucket build, the TrainStep warm path
behind Model.fit(resume=True), cache-key invalidation (changing ANY key
component must MISS — a stale hit silently serving the wrong program is
the failure mode to prove impossible), the process-global conflict
warning, and the chaos tier's corrupt-entry fallback.
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import compile_cache
from paddle_tpu.jit.compile_cache import ExecutableStore

import jax
import jax.numpy as jnp


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    from paddle_tpu.models.gpt import gpt
    m = gpt("test-tiny")
    m.eval()
    return m


def _serve_cfg(m, max_new=6, buckets=(16, 32), max_batch=2):
    from paddle_tpu.inference import Config
    spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
    return (Config().from_layer(m, spec)
            .enable_generation(max_new_tokens=max_new,
                               prefill_buckets=buckets,
                               max_batch=max_batch))


# ------------------------------------------------------------- the store


def test_store_roundtrip_and_metrics(tmp_path):
    """Cold miss compiles + persists; a fresh lookup deserializes
    (hit); both executables compute the same thing; every event lands
    in the jit.compile_cache.* counters."""
    from paddle_tpu.core import monitor
    store = ExecutableStore(str(tmp_path / "exe"))

    def f(x):
        return x * 2 + 1

    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    monitor.enable()
    try:
        h0 = _counter("jit.compile_cache.hits")
        m0 = _counter("jit.compile_cache.misses")
        b0 = _counter("jit.compile_cache.bytes")
        exe = store.get_or_compile(jax.jit(f).lower(aval), label="t")
        assert store.stats["misses"] == 1 and store.stats["hits"] == 0
        assert store.stats["saves"] == 1 and len(store) == 1
        exe2 = store.get_or_compile(jax.jit(f).lower(aval), label="t")
        assert store.stats["hits"] == 1 and store.stats["misses"] == 1
        assert _counter("jit.compile_cache.hits") - h0 == 1
        assert _counter("jit.compile_cache.misses") - m0 == 1
        assert _counter("jit.compile_cache.bytes") - b0 > 0
    finally:
        monitor.disable()
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  np.asarray(exe2(x)))


def test_cache_key_invalidation():
    """Changing any key component — program, donation signature, mesh
    axes, jax/jaxlib version, backend platform/device/count — must
    produce a different key (MISS). Identical programs from fresh
    traces must produce the SAME key (the warm-restart hit)."""
    store = ExecutableStore("/tmp/never-written-key-test")

    def f(x):
        return x + 1

    def g(x):
        return x + 2

    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    base = store.key_for(jax.jit(f).lower(aval))
    # deterministic across fresh traces of the same program
    assert store.key_for(jax.jit(f).lower(aval)) == base
    # a different program misses
    assert store.key_for(jax.jit(g).lower(aval)) != base
    # ...and a different shape is a different program
    assert store.key_for(
        jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32))) != base
    low = jax.jit(f).lower(aval)
    # donation signature
    assert store.key_for(low, extra=dict(donation=(0,))) != base
    assert store.key_for(low, extra=dict(donation=(0,))) != \
        store.key_for(low, extra=dict(donation=(1,)))
    # mesh axes (the DistributedTrainStep warm path's extra)
    assert store.key_for(low, extra=dict(mesh=(("dp", 8),))) != \
        store.key_for(low, extra=dict(mesh=(("dp", 4), ("mp", 2))))
    # environment half: jaxlib / jax / backend / device flavor / count
    assert store.key_for(low, jaxlib_version="9.9.9") != base
    assert store.key_for(low, jax_version="9.9.9") != base
    assert store.key_for(low, backend="tpu") != base
    assert store.key_for(low, device_kind="TPU v5e") != base
    assert store.key_for(low, n_devices=256) != base


def test_enable_compile_cache_conflict_warns(tmp_path):
    """Process-global set-once + warn-on-conflict semantics — the
    predictor's original `_ensure_compile_cache` contract, now owned by
    the one shared implementation."""
    prev_dir = compile_cache._CACHE_DIR
    prev_store = compile_cache._DEFAULT_STORE
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    try:
        if prev_dir is None:
            store = compile_cache.enable_compile_cache(a)
            assert isinstance(store, ExecutableStore)
            assert compile_cache.cache_dir() == a
            current = a
        else:  # some earlier test already anchored the process cache
            current = prev_dir
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            compile_cache.enable_compile_cache(b)
        assert any("process-global" in str(x.message) for x in w)
        assert compile_cache.cache_dir() == current
        # re-naming the SAME dir is silent (idempotent re-entry)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            compile_cache.enable_compile_cache(current)
        assert not w
    finally:
        if prev_dir is None:
            # undo the jax-global side effect so later tests don't
            # write cache entries into this test's tmp dir
            jax.config.update("jax_compilation_cache_dir", prev_dir)  # lint: compile-cache-dir-ok (test restore)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)
        compile_cache._CACHE_DIR = prev_dir
        compile_cache.set_default_store(prev_store)


# ----------------------------------------------- the traceless manifest


def test_manifest_hit_skips_tracing(tmp_path):
    """A manifest (signature) hit deserializes WITHOUT calling
    lower_fn — zero traces, zero compiles; a changed signature falls
    back to the traced path (which still resolves to the same
    executable by its HLO key and heals the manifest)."""
    root = str(tmp_path / "exe")
    store = ExecutableStore(root)

    def f(x):
        return x * 5.0

    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    sig = dict(kind="t", operands=compile_cache.aval_signature((aval,)))
    exe = store.get_or_build(sig, lambda: jax.jit(f).lower(aval))
    assert store.stats["misses"] == 1 and len(store.refs()) == 1

    def boom():
        raise AssertionError("manifest hit must not trace")

    warm = ExecutableStore(root)
    exe2 = warm.get_or_build(sig, boom)
    assert warm.stats["hits"] == 1 and warm.stats["misses"] == 0
    x = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  np.asarray(exe2(x)))
    # changed signature: traced fallback, same executable, new ref
    exe3 = warm.get_or_build(dict(sig, kind="other"),
                             lambda: jax.jit(f).lower(aval))
    assert warm.stats["hits"] == 2 and warm.stats["misses"] == 0
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  np.asarray(exe3(x)))
    assert len(warm.refs()) == 2
    # signature=None (no sound structural key): traced path, still hits
    exe4 = warm.get_or_build(None, lambda: jax.jit(f).lower(aval))
    assert warm.stats["hits"] == 3
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  np.asarray(exe4(x)))


def test_verify_mode_catches_poisoned_ref(tmp_path, monkeypatch):
    """PADDLE_COMPILE_CACHE_VERIFY=1: a manifest entry disagreeing with
    the program's real fingerprint is recorded as
    misses{cause=stale_ref}, the CORRECT program is served, and the ref
    is repaired in place."""
    from paddle_tpu.core import monitor
    root = str(tmp_path / "exe")
    store = ExecutableStore(root)

    def f(x):
        return x + 1.0

    def g(x):
        return x * 100.0

    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    sig_f = dict(kind="f", operands=compile_cache.aval_signature((aval,)))
    store.get_or_build(sig_f, lambda: jax.jit(f).lower(aval))
    key_g = store.key_for(jax.jit(g).lower(aval))
    store.get_or_compile(jax.jit(g).lower(aval))
    # poison the manifest: f's signature now points at g's executable —
    # an unverified lookup would serve the WRONG program
    store._write_ref(
        compile_cache._signature_key(sig_f, None), key_g)
    x = jnp.ones((4,), jnp.float32)
    lied = ExecutableStore(root).get_or_build(
        sig_f, lambda: jax.jit(f).lower(aval))
    assert float(np.asarray(lied(x))[0]) == 100.0   # the lie, shown

    monkeypatch.setenv("PADDLE_COMPILE_CACHE_VERIFY", "1")
    fixed = ExecutableStore(root)
    monitor.enable()
    try:
        s0 = _counter("jit.compile_cache.misses{cause=stale_ref}")
        exe = fixed.get_or_build(sig_f, lambda: jax.jit(f).lower(aval))
        assert _counter(
            "jit.compile_cache.misses{cause=stale_ref}") - s0 == 1
    finally:
        monitor.disable()
    assert float(np.asarray(exe(x))[0]) == 2.0      # truth restored
    # the ref was repaired: a clean unverified lookup is correct now
    monkeypatch.delenv("PADDLE_COMPILE_CACHE_VERIFY")
    healed = ExecutableStore(root).get_or_build(
        sig_f, lambda: (_ for _ in ()).throw(
            AssertionError("repaired ref must resolve tracelessly")))
    assert float(np.asarray(healed(x))[0]) == 2.0


# ------------------------------------------------- THE warm-restart gate


def _run_traffic(engine):
    from paddle_tpu.serving import RequestParams
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 512, n).astype(np.int32)
               for n in (5, 12, 20)]
    handles = [engine.submit(p, RequestParams(max_new_tokens=6))
               for p in prompts]
    while engine.busy:
        engine.step()
    return [h.tokens for h in handles]


def test_warm_restart_gate_serving(tiny_gpt, tmp_path):
    """THE tier-1 gate: one cold warmup populates the store; a rebuilt
    engine in a cleared-jax-cache state loads EVERY program from the
    store — jit.compile_cache.hits == program count, misses == 0, zero
    XLA compiles — and serves traffic bitwise-equal to the cold
    reference."""
    from paddle_tpu.core import monitor
    from paddle_tpu.serving import ServingEngine
    root = str(tmp_path / "exe")
    n_programs = 2 + 4   # a prefill per bucket + decode/admit/free/poll_view

    cold_store = ExecutableStore(root)
    cold = ServingEngine(_serve_cfg(tiny_gpt), poll_every=2,
                         executable_store=cold_store)
    assert cold_store.stats["misses"] == n_programs
    assert cold_store.stats["hits"] == 0
    assert len(cold_store) == n_programs       # all persisted
    assert len(cold_store.refs()) == n_programs  # manifest written too
    ref = _run_traffic(cold)
    assert cold_store.stats["misses"] == n_programs  # no compile under
    #                                                  traffic either

    # "relaunch": drop every in-memory trace/compile cache; only the
    # on-disk store survives — exactly what a fresh process sees
    jax.clear_caches()
    warm_store = ExecutableStore(root)
    monitor.enable()
    try:
        h0 = _counter("jit.compile_cache.hits")
        m0 = _counter("jit.compile_cache.misses")
        warm = ServingEngine(_serve_cfg(tiny_gpt), poll_every=2,
                             executable_store=warm_store)
        assert _counter("jit.compile_cache.hits") - h0 == n_programs
        assert _counter("jit.compile_cache.misses") - m0 == 0
    finally:
        monitor.disable()
    assert warm_store.stats["hits"] == n_programs
    assert warm_store.stats["misses"] == 0     # zero XLA compiles
    out = _run_traffic(warm)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)    # bitwise-equal serving


def test_predictor_bucket_build_warm(tiny_gpt, tmp_path):
    """The Predictor's per-bucket (prefill, decode) AOT build loads
    from the store on relaunch and generates identically."""
    from paddle_tpu.inference import create_predictor
    store = ExecutableStore(str(tmp_path / "exe"))
    prev = compile_cache.set_default_store(store)
    try:
        p1 = create_predictor(
            _serve_cfg(tiny_gpt, buckets=(16,), max_batch=2))
        assert store.stats["misses"] == 2   # prefill + decode
        ref = p1.generate([[1, 2, 3]], max_new_tokens=4, seed=0)

        jax.clear_caches()
        store2 = ExecutableStore(store.root)
        compile_cache.set_default_store(store2)
        p2 = create_predictor(
            _serve_cfg(tiny_gpt, buckets=(16,), max_batch=2))
        assert store2.stats["hits"] == 2
        assert store2.stats["misses"] == 0
        out = p2.generate([[1, 2, 3]], max_new_tokens=4, seed=0)
        np.testing.assert_array_equal(ref[0], out[0])
    finally:
        compile_cache.set_default_store(prev)


def test_trainstep_warm_start(tmp_path):
    """The fit(resume=True) warm path: a rebuilt TrainStep loads the
    fused-step executable (hits == 1, misses == 0), its first loss is
    bitwise-equal to the cold run's, and a drifted operand signature
    falls back to the jit path instead of erroring."""
    from paddle_tpu import nn, optimizer

    def build():
        paddle.seed(11)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                          nn.Linear(16, 4))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=m.parameters())
        ce = nn.CrossEntropyLoss()
        return paddle.jit.TrainStep(m, opt, lambda out, lbl: ce(out, lbl))

    rng = np.random.RandomState(0)
    xa = rng.randn(4, 8).astype(np.float32)
    ya = rng.randint(0, 4, (4,)).astype(np.int64)

    store = ExecutableStore(str(tmp_path / "exe"))
    step = build().enable_warm_start(store)
    cold = float(step(paddle.to_tensor(xa), paddle.to_tensor(ya)))
    assert store.stats["misses"] == 1 and store.stats["saves"] == 1

    jax.clear_caches()
    store2 = ExecutableStore(store.root)
    step2 = build().enable_warm_start(store2)
    warm = float(step2(paddle.to_tensor(xa), paddle.to_tensor(ya)))
    assert store2.stats["hits"] == 1 and store2.stats["misses"] == 0
    assert warm == cold     # identical init (same seed) + same program
    assert step2._warm_exe is not None
    # steps keep dispatching the warmed executable...
    float(step2(paddle.to_tensor(xa), paddle.to_tensor(ya)))
    assert step2._warm_exe is not None
    # ...until the operand signature drifts: clean fallback to jit
    xb = rng.randn(6, 8).astype(np.float32)
    yb = rng.randint(0, 4, (6,)).astype(np.int64)
    drift = float(step2(paddle.to_tensor(xb), paddle.to_tensor(yb)))
    assert np.isfinite(drift) and step2._warm_exe is None


def test_trainstep_warm_multi_step_loss_curve(tmp_path):
    """Repeated dispatch of a warm-loaded fused step — the bug class
    this pins: a serialized executable REPLAYS its donation aliasing
    on load, and deserialized-on-CPU aliasing double-frees the donated
    buffers (heap corruption on the second call). The AOT path bakes
    donation only where the backend implements it, so a warm relaunch
    replays the cold run's loss curve bitwise."""
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import gpt

    def losses(store):
        paddle.seed(5)
        m = gpt("test-tiny", max_position_embeddings=32)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=m.parameters())
        step = paddle.jit.TrainStep(m, opt,
                                    lambda lg, y: m.loss(lg, y))
        step.enable_warm_start(store)
        ids = np.random.RandomState(0).randint(
            0, m.cfg.vocab_size, (2, 32)).astype(np.int32)
        x = paddle.to_tensor(ids)
        y = paddle.to_tensor(ids.astype(np.int64))
        return [float(step(x, y)) for _ in range(4)]

    root = str(tmp_path / "exe")
    cold = losses(ExecutableStore(root))
    assert cold[-1] < cold[0]          # it actually trains
    jax.clear_caches()
    store = ExecutableStore(root)
    warm = losses(store)
    assert store.stats["hits"] == 1 and store.stats["misses"] == 0
    assert warm == cold                # bitwise-equal 4-step curve


def test_distributed_trainstep_warm_start(tmp_path):
    """The sharded step's warm path on the 8-device CPU mesh: a rebuilt
    DistributedTrainStep loads its executable (hits == 1, misses == 0)
    and replays the cold loss curve bitwise; the mesh axes are part of
    the key."""
    from paddle_tpu import distributed as dist, nn, optimizer
    from paddle_tpu.distributed import fleet
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    root = str(tmp_path / "exe")
    rng = np.random.RandomState(3)
    xs = rng.randn(16, 8).astype(np.float32)
    ys = rng.randn(16, 2).astype(np.float32)
    try:
        fleet.init(strategy=fleet.DistributedStrategy(
            hybrid_configs={"dp_degree": 8}))

        def losses(store):
            paddle.seed(7)
            m = nn.Linear(8, 2)
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=m.parameters())
            step = fleet.DistributedTrainStep(
                m, opt, nn.functional.mse_loss)
            step.enable_warm_start(store)
            return [float(step(paddle.to_tensor(xs),
                               paddle.to_tensor(ys)))
                    for _ in range(3)]

        cold = losses(ExecutableStore(root))
        assert cold[-1] < cold[0]
        jax.clear_caches()
        store = ExecutableStore(root)
        warm = losses(store)
        assert store.stats["hits"] == 1 and store.stats["misses"] == 0
        assert warm == cold
    finally:
        dist.set_hybrid_communicate_group(None)


def test_fit_resume_enables_warm_start(tmp_path):
    """Model.fit(resume=...) is the opt-in: with a store active, the
    fused step warm-starts (and persists its executable for the next
    relaunch); without resume, fit never touches the store."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.hapi import Model
    store = ExecutableStore(str(tmp_path / "exe"))
    prev = compile_cache.set_default_store(store)
    try:
        def build():
            paddle.seed(3)
            net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                nn.Linear(8, 2))
            m = Model(net)
            m.prepare(optimizer.SGD(learning_rate=0.01,
                                    parameters=net.parameters()),
                      nn.CrossEntropyLoss())
            return m

        rng = np.random.RandomState(0)
        data = [([rng.randn(2, 4).astype(np.float32)],
                 [rng.randint(0, 2, (2,)).astype(np.int64)])
                for _ in range(3)]
        # no resume: the store is never consulted
        build().fit(train_data=data, epochs=1, verbose=0)
        assert store.stats == dict(hits=0, misses=0, saves=0,
                                   bytes_loaded=0, bytes_saved=0)
        # resume (fresh start — no checkpoint yet): warm path active,
        # cold store populated
        m = build()
        m.fit(train_data=data, epochs=1, verbose=0,
              resume=str(tmp_path / "ckpt"))
        assert m._train_step._warm_exe is not None
        assert store.stats["saves"] == 1
        # relaunch: the step executable loads instead of compiling
        m2 = build()
        m2.fit(train_data=data, epochs=1, verbose=0,
               resume=str(tmp_path / "ckpt"))
        assert store.stats["hits"] == 1
    finally:
        compile_cache.set_default_store(prev)


# ------------------------------------------------------------ chaos tier


@pytest.mark.chaos
class TestCorruptEntryFallback:
    """A bad store entry must NEVER crash a relaunch: the load falls
    back to a fresh compile, records misses{cause=corrupt}, drops the
    bad entry, and rewrites a good one (the CheckpointManager
    corruption-fallback idiom applied to executables)."""

    def _seed_store(self, tmp_path):
        store = ExecutableStore(str(tmp_path / "exe"))

        def f(x):
            return (x * 3.0).sum()

        aval = jax.ShapeDtypeStruct((16,), jnp.float32)
        store.get_or_compile(jax.jit(f).lower(aval))
        assert len(store) == 1
        return store, f, aval

    def test_truncated_entry_recompiles_and_rewrites(self, tmp_path):
        from paddle_tpu.core import monitor
        from paddle_tpu.utils import fault_injection as fi
        store, f, aval = self._seed_store(tmp_path)
        fi.truncate_executable(store, keep_bytes=7)  # torn write
        monitor.enable()
        try:
            c0 = _counter("jit.compile_cache.misses{cause=corrupt}")
            exe = store.get_or_compile(jax.jit(f).lower(aval))
            assert _counter(
                "jit.compile_cache.misses{cause=corrupt}") - c0 == 1
        finally:
            monitor.disable()
        x = jnp.arange(16, dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(exe(x)),
                                      np.asarray(jax.jit(f)(x)))
        # a good entry was rewritten: the next relaunch hits clean
        store2 = ExecutableStore(store.root)
        assert store2.load(
            store2.key_for(jax.jit(f).lower(aval))) is not None
        assert store2.stats["hits"] == 1 and store2.stats["misses"] == 0

    def test_bitflipped_entry_checksum_catches(self, tmp_path):
        from paddle_tpu.utils import fault_injection as fi
        store, f, aval = self._seed_store(tmp_path)
        fi.corrupt_executable(store)                 # bit rot in payload
        fresh = ExecutableStore(store.root)
        key = fresh.key_for(jax.jit(f).lower(aval))
        assert fresh.load(key) is None               # checksum caught it
        assert fresh.stats["misses"] == 1
        assert len(fresh) == 0                       # bad entry dropped
        # the recompile path still produces a working executable
        exe = fresh.get_or_compile(jax.jit(f).lower(aval))
        x = jnp.ones((16,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(exe(x)),
                                      np.asarray(jax.jit(f)(x)))

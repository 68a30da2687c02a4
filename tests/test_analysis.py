"""Program-auditor coverage (paddle_tpu.analysis): golden fixtures of
deliberately bad programs (each seeded defect must be reported with the
right severity and source location), the audit API surface, collective
accounting cross-checked against the runtime counters, and — the tier-1
acceptance gates — audits of the flagship programs: TrainStep,
DistributedTrainStep on the dryrun hybrid mesh, the generation
prefill/decode pair, and the Predictor's AOT bucket executables, with
zero ERROR findings and full donation coverage asserted."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis, optimizer
from paddle_tpu.analysis import Severity
from paddle_tpu.core import monitor
from paddle_tpu.profiler import metrics


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.disable()
    metrics.reset()
    yield
    metrics.disable()
    metrics.reset()


# ------------------------------------------------- golden bad programs
# Each fixture seeds exactly one defect; the auditor must report it with
# the right check id, severity, and (where an equation exists) a source
# location pointing INTO this file.


def _fixture_missed_donation(params, batch):
    return [p - 0.1 * batch.sum() for p in params]


def _fixture_hidden_io_callback(x):
    jax.experimental.io_callback(
        lambda a: None, None, x, ordered=True)
    return x * 2


def _fixture_fp64_leak(x):
    return x.astype(jnp.float64) * 2.0


_BIG_CONST = None  # lazily built 8 MiB array (module import stays cheap)


def _fixture_baked_constant(x):
    global _BIG_CONST
    if _BIG_CONST is None:
        _BIG_CONST = np.ones((1024, 2048), np.float32)  # 8 MiB
    return x @ jnp.asarray(_BIG_CONST)


def _fixture_bf16_promotion(x):
    y = x * np.float32(1.5)  # f32 scalar re-widens the bf16 block
    return y.sum()


class TestGoldenFixtures:
    def test_missed_donation(self):
        params = [jnp.zeros((128, 128)), jnp.zeros((64, 64))]
        report = analysis.audit(_fixture_missed_donation, params,
                                jnp.ones((8, 16)))
        misses = report.by_check("donation.miss")
        assert len(misses) == 2
        assert all(f.severity == Severity.WARNING for f in misses)
        assert report.donation_coverage == 0.0
        sizes = sorted(f.data["bytes"] for f in misses)
        assert sizes == [64 * 64 * 4, 128 * 128 * 4]
        # donating repairs it
        fixed = analysis.audit(_fixture_missed_donation, params,
                               jnp.ones((8, 16)), donate=(0,))
        assert not fixed.by_check("donation.miss")
        assert fixed.donation_coverage == 1.0

    def test_hidden_io_callback(self):
        report = analysis.audit(_fixture_hidden_io_callback,
                                jnp.ones((4,)))
        hits = report.by_check("host_sync.callback")
        assert len(hits) == 1
        assert hits[0].severity == Severity.ERROR
        assert "io_callback" in hits[0].message
        assert "test_analysis.py" in hits[0].source
        with pytest.raises(analysis.AuditError, match="io_callback"):
            report.raise_on_error()

    def test_debug_print_is_warning_not_error(self):
        def prog(x):
            jax.debug.print("x={x}", x=x)
            return x + 1

        report = analysis.audit(prog, jnp.ones((4,)))
        hits = report.by_check("host_sync.callback")
        assert len(hits) == 1
        assert hits[0].severity == Severity.WARNING
        report.raise_on_error()  # warnings don't fail the gate

    def test_fp64_leak(self):
        try:
            jax.config.update("jax_enable_x64", True)
            report = analysis.audit(_fixture_fp64_leak,
                                    jnp.ones((8,), jnp.float32))
        finally:
            jax.config.update("jax_enable_x64", False)
        errs = report.by_check("dtype.fp64")
        assert errs and all(f.severity == Severity.ERROR for f in errs)
        assert any("test_analysis.py" in f.source for f in errs)

    def test_baked_constant_over_budget(self):
        report = analysis.audit(_fixture_baked_constant,
                                jnp.ones((4, 1024)))
        hits = report.by_check("const.baked")
        assert len(hits) == 1
        assert hits[0].severity == Severity.ERROR
        assert hits[0].data["bytes"] == 8 * 1024 * 1024
        # a budget above the const passes
        ok = analysis.audit(_fixture_baked_constant, jnp.ones((4, 1024)),
                            const_budget_bytes=16 * 1024 * 1024)
        assert not ok.by_check("const.baked")

    def test_fp32_promotion_in_bf16_block(self):
        report = analysis.audit(_fixture_bf16_promotion,
                                jnp.ones((8, 8), jnp.bfloat16),
                                bf16_compute=True)
        hits = report.by_check("dtype.bf16_upcast")
        assert hits and all(f.severity == Severity.WARNING for f in hits)
        assert any("test_analysis.py" in f.source for f in hits)
        # the same program is CLEAN without the declared-bf16 contract
        plain = analysis.audit(_fixture_bf16_promotion,
                               jnp.ones((8, 8), jnp.bfloat16))
        assert not plain.by_check("dtype.bf16_upcast")


# ------------------------------------------------------------ audit api


class TestAuditAPI:
    def test_checks_subset_and_unknown_check(self):
        report = analysis.audit(_fixture_hidden_io_callback,
                                jnp.ones((4,)), checks=("constants",))
        assert not report.by_check("host_sync")  # pass not selected
        with pytest.raises(ValueError, match="unknown detector"):
            analysis.audit(lambda x: x, jnp.ones((2,)),
                           checks=("nope",))

    def test_allow_suppresses_to_info(self):
        report = analysis.audit(
            _fixture_hidden_io_callback, jnp.ones((4,)),
            allow=("host_sync",))
        hits = report.by_check("host_sync.callback")
        assert hits and hits[0].severity == Severity.INFO
        assert hits[0].data.get("allowed")
        report.raise_on_error()  # suppressed: the gate passes
        # a scoped allow that does NOT match keeps the error
        strict = analysis.audit(
            _fixture_hidden_io_callback, jnp.ones((4,)),
            allow=("host_sync@some_other_file.py",))
        assert strict.errors

    def test_findings_counted_into_monitor(self):
        metrics.enable()
        analysis.audit(_fixture_hidden_io_callback, jnp.ones((4,)))
        snap = metrics.snapshot()
        key = ("analysis.findings{check=host_sync.callback,"
               "severity=ERROR}")
        assert snap[key]["value"] == 1
        assert snap["analysis.findings"]["value"] >= 1

    def test_register_detector(self):
        def too_many_eqns(ctx):
            from paddle_tpu.analysis.jaxpr_utils import walk_eqns
            n = sum(1 for _ in walk_eqns(ctx.closed_jaxpr))
            return [analysis.Finding("custom.eqn_budget",
                                     Severity.WARNING,
                                     f"{n} eqns")] if n > 1 else []

        analysis.register_detector("custom_eqn_budget", too_many_eqns)
        try:
            report = analysis.audit(lambda x: x * 2 + 1, jnp.ones((4,)))
            assert report.by_check("custom.eqn_budget")
            with pytest.raises(ValueError, match="already registered"):
                analysis.register_detector("custom_eqn_budget",
                                           too_many_eqns)
        finally:
            del analysis.DETECTORS["custom_eqn_budget"]

    def test_out_shape_exposed_from_the_same_trace(self):
        """report.out_shape == eval_shape of the program, recovered
        from the audit's own trace (chained audits never re-trace)."""
        report = analysis.audit(lambda x: (x * 2, x.sum()),
                                jnp.ones((4,), jnp.float32))
        a, b = report.out_shape
        assert a.shape == (4,) and b.shape == ()
        assert a.dtype == jnp.float32

    def test_unchecked_donation_coverage_raises(self):
        """A report whose audit excluded the donation pass must not
        satisfy a coverage gate with a vacuous 1.0."""
        report = analysis.audit(_fixture_missed_donation,
                                [jnp.zeros((64, 64))], jnp.ones((8,)),
                                checks=("host_sync",))
        assert not report.donation_checked
        with pytest.raises(ValueError, match="without the donation"):
            _ = report.donation_coverage
        assert "n/a" in report.summary()  # summary still printable

    def test_generation_audit_name_override(self):
        from paddle_tpu.generation.api import GenerationSession
        model = _tiny_gpt()
        sess = GenerationSession(model)
        pre, dec = sess.audit(2, 16, 128, name="bucket16")
        assert pre.name == "bucket16.prefill"
        assert dec.name == "bucket16.decode"

    def test_abstract_inputs_never_execute(self):
        calls = []

        def prog(x):
            calls.append(1)  # runs at TRACE time only
            return x + 1

        sds = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        report = analysis.audit(prog, sds)
        assert report.findings == [] and calls == [1]


# -------------------------------------------- collective accounting


class TestCollectiveAccounting:
    @pytest.fixture(autouse=True)
    def _default_world_mesh(self):
        from paddle_tpu.distributed import topology
        prev = topology.get_hybrid_communicate_group()
        topology.set_hybrid_communicate_group(None)
        yield
        topology.set_hybrid_communicate_group(prev)

    def _world_psum(self):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("world",))
        return shard_map(lambda a: jax.lax.psum(a, "world"), mesh=mesh,
                         in_specs=P("world"), out_specs=P("world"),
                         check_vma=False)

    def test_static_bytes_match_measured_counters(self):
        """The static per-axis accounting equals what one real
        execution records into comm.bytes{axis=...} — the PR-2
        cross-check the detector exists for."""
        from paddle_tpu.distributed import collective
        metrics.enable()
        x = paddle.ones([8, 8])
        collective.all_reduce(x)
        snap = metrics.snapshot()
        metrics.disable()

        report = analysis.audit(self._world_psum(), jnp.ones((8, 8)))
        assert report.collectives == {"world": 8 * 8 * 4}
        checked = analysis.cross_check_collectives(report, snap)
        assert not checked.by_check("collective.mismatch")

    def test_cross_check_flags_divergence(self):
        report = analysis.audit(self._world_psum(), jnp.ones((8, 8)))
        fake = {"comm.bytes{axis=world,op=all_reduce}": {"value": 999}}
        checked = analysis.cross_check_collectives(report, fake)
        bad = checked.by_check("collective.mismatch")
        assert bad and bad[0].severity == Severity.WARNING
        assert bad[0].data == {"axis": "world", "static": 256,
                               "measured": 999}

    def test_cross_check_refuses_unchecked_report(self):
        """A report whose audit EXCLUDED the collectives pass has no
        static accounting — cross-checking it must raise, not report a
        spurious 0-vs-measured mismatch."""
        report = analysis.audit(self._world_psum(), jnp.ones((8, 8)),
                                checks=("host_sync",))
        assert not report.collectives_checked
        fake = {"comm.bytes{axis=world,op=all_reduce}": {"value": 256}}
        with pytest.raises(ValueError, match="without the 'collectives'"):
            analysis.cross_check_collectives(report, fake)


# ------------------------------------------------- flagship tier-1 gates


def _tiny_gpt():
    from paddle_tpu.models.gpt import gpt
    paddle.seed(0)
    return gpt("test-tiny")


class TestFlagshipGates:
    """THE audit gates: the invariants PRs 2-6 established, enforced
    statically on every flagship program. Zero ERROR findings; donation
    coverage 1.0 for train state and the KV cache."""

    def test_train_step_audit_clean(self):
        model = _tiny_gpt()
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        from paddle_tpu.jit.api import TrainStep
        step = TrainStep(model, opt,
                         lambda out, lbl: model.loss(out, lbl))
        ids = np.random.RandomState(0).randint(
            0, 512, (2, 16)).astype(np.int32)
        report = step.audit(paddle.to_tensor(ids),
                            paddle.to_tensor(ids.astype(np.int64)))
        report.raise_on_error()
        assert not report.by_check("host_sync")
        assert not report.by_check("donation.miss")
        # params + optimizer state fully donated: in-place HBM updates
        assert report.donation_coverage == 1.0
        # ISSUE-14: every flagship audit carries a memory plan
        assert report.memory is not None
        assert report.memory.peak_bytes > 0

    def test_distributed_step_audit_clean(self):
        from paddle_tpu.distributed import fleet, topology
        from paddle_tpu.models.ernie import ernie
        prev = topology.get_hybrid_communicate_group()
        try:
            paddle.seed(0)
            fleet.init(strategy=fleet.DistributedStrategy(
                hybrid_configs={"mp_degree": 2}))
            model = ernie("test-tiny")
            opt = optimizer.AdamW(learning_rate=1e-3,
                                  parameters=model.parameters())
            step = fleet.DistributedTrainStep(
                model, opt, lambda out, lab: model.loss(out, lab))
            rng = np.random.RandomState(0)
            ids = paddle.to_tensor(
                rng.randint(0, 512, (4, 16)).astype(np.int32))
            labels = (
                paddle.to_tensor(
                    rng.randint(0, 512, (4, 16)).astype(np.int64)),
                paddle.to_tensor(
                    rng.randint(0, 2, (4,)).astype(np.int64)))
            report = step.audit(ids, labels)
        finally:
            topology.set_hybrid_communicate_group(prev)
        report.raise_on_error()
        assert not report.by_check("donation.miss")
        assert report.donation_coverage == 1.0
        assert report.memory is not None           # ISSUE-14 threading

    def test_generation_pair_audit_clean(self):
        from paddle_tpu.generation.api import GenerationSession
        model = _tiny_gpt()
        sess = GenerationSession(model)
        # a mid-fit audit must trace the EVAL program, exactly like
        # every dispatch path (train-mode dropout baked into the traced
        # jaxpr would gate a program that is never served)
        model.train()
        prefill, decode = sess.audit(2, 16, 128)
        assert not model.training
        prefill.raise_on_error()
        decode.raise_on_error()
        for rep in (prefill, decode):
            assert not rep.by_check("host_sync")
            assert not rep.by_check("const.baked")
        # the KV cache is donated through the decode step (audited at
        # the TPU intent even on the CPU test backend)
        assert decode.donation_coverage == 1.0
        assert not decode.by_check("donation.miss")
        # ISSUE-14: the pair carries memory plans, and donation keeps
        # the decode peak below two cache copies' worth of growth
        assert prefill.memory is not None and decode.memory is not None
        assert decode.memory.donated_bytes > 0

    def test_predictor_bucket_audit_clean(self):
        from paddle_tpu.inference import Config, create_predictor
        model = _tiny_gpt()
        ids = np.random.RandomState(0).randint(
            0, 512, (2, 16)).astype(np.int32)
        cfg = Config().from_layer(
            model, input_spec=[paddle.to_tensor(ids)])
        cfg.enable_generation(max_new_tokens=6,
                              prefill_buckets=(16, 32),
                              max_batch=2, eos_token_id=None)
        pred = create_predictor(cfg)
        reports = pred.audit_generation()
        assert set(reports) == {("prefill", 16), ("decode", 16),
                                ("prefill", 32), ("decode", 32)}
        for key, rep in reports.items():
            rep.raise_on_error()
            if key[0] == "decode":
                assert rep.donation_coverage == 1.0
            assert rep.memory is not None          # ISSUE-14 threading
        pred.audit_forward().raise_on_error()

    def test_predictor_audit_mirrors_serving_precision(self):
        """Under a low-precision config, run() casts floating feeds to
        bf16 before dispatch; audit_forward must trace THAT program —
        bf16 inputs, bf16 outputs — not the declared-fp32 one."""
        from paddle_tpu.inference import Config, PrecisionType, \
            create_predictor
        from paddle_tpu import nn
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.ReLU())
        x = paddle.to_tensor(np.zeros((2, 8), np.float32))
        cfg = Config().from_layer(net, input_spec=[x])
        cfg.enable_tpu(precision=PrecisionType.Bfloat16)
        pred = create_predictor(cfg)
        report = pred.audit_forward()
        report.raise_on_error()
        out_dtypes = {np.dtype(s.dtype).name
                      for s in jax.tree_util.tree_leaves(report.out_shape)}
        assert out_dtypes == {"bfloat16"}

    def test_audit_catches_seeded_regression(self):
        """Sanity that the gates FAIL when a flagship program actually
        regresses: a TrainStep whose step_fn sneaks in a pure_callback
        must produce an ERROR (the gate is not vacuously green)."""
        model = _tiny_gpt()
        opt = optimizer.SGD(learning_rate=1e-2,
                            parameters=model.parameters())
        from paddle_tpu.jit.api import TrainStep
        step = TrainStep(model, opt,
                         lambda out, lbl: model.loss(out, lbl))
        inner = step._step_fn

        def poisoned(params, opt_state, lr, step_no, *batch):
            jax.pure_callback(lambda: np.float32(0.0),
                              jax.ShapeDtypeStruct((), np.float32))
            return inner(params, opt_state, lr, step_no, *batch)

        step._step_fn = poisoned
        ids = np.random.RandomState(0).randint(
            0, 512, (2, 16)).astype(np.int32)
        report = step.audit(paddle.to_tensor(ids),
                            paddle.to_tensor(ids.astype(np.int64)))
        assert report.errors
        with pytest.raises(analysis.AuditError, match="pure_callback"):
            report.raise_on_error()

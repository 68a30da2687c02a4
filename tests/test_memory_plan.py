"""Static HBM planner coverage (analysis.memory / ISSUE 14): exact-byte
golden fixtures (including the int8-cache + bf16-sidecar and
int4-packed-weight quant geometries), the donation credit, the
``mem.budget`` gate (audit kwarg + ``PADDLE_HBM_BUDGET``) with a seeded
undonated-cache regression proving it non-vacuous, predicted-vs-
measured slack on the CPU test-tiny decode and engine programs, the
ServingEngine budget fail-fast + health() headroom, and
``cross_check_memory``.

Documented CPU slack (asserted below): the plan never under-counts the
program's RESIDENT set (inputs held live + outputs produced), and it
over-predicts by at most ``_SLACK``x — the gap is transient
temporaries XLA materializes and frees between the live-array polls the
CPU backend's ``max_memory_allocated`` fallback can see.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis, device, optimizer
from paddle_tpu.analysis import Severity
from paddle_tpu.profiler import metrics

# predicted peak within [1x, _SLACK x] of the measured resident set on
# the CPU test-tiny decode/engine programs (see module docstring)
_SLACK = 2.0


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.disable()
    metrics.reset()
    yield
    metrics.disable()
    metrics.reset()


def _tiny_gpt():
    from paddle_tpu.models.gpt import gpt
    paddle.seed(0)
    return gpt("test-tiny")


def _bytes_of(tree) -> int:
    return sum(
        int(np.prod(l.shape, dtype=np.int64))
        * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree)
        if hasattr(l, "shape"))


# ------------------------------------------------------- byte arithmetic


class TestParseBytes:
    def test_suffixes_and_plain(self):
        assert analysis.parse_bytes(12345) == 12345
        assert analysis.parse_bytes("16GiB") == 16 << 30
        assert analysis.parse_bytes("16G") == 16 << 30
        assert analysis.parse_bytes("512M") == 512 << 20
        assert analysis.parse_bytes("1.5k") == 1536
        assert analysis.parse_bytes(" 64 KiB ") == 64 << 10

    def test_garbage_and_nonpositive_raise(self):
        # 'inf'/nan overflow int() with OverflowError — must fold into
        # ValueError or every swallow path built on it crashes instead
        for bad in ("lots", "", "-1G", 0, -5, "inf", "1e500",
                    float("inf"), float("nan")):
            with pytest.raises(ValueError):
                analysis.parse_bytes(bad)

    def test_env_resolution(self, monkeypatch):
        monkeypatch.delenv("PADDLE_HBM_BUDGET", raising=False)
        assert analysis.resolve_hbm_budget() is None
        assert analysis.resolve_hbm_budget("1M") == 1 << 20
        monkeypatch.setenv("PADDLE_HBM_BUDGET", "2MiB")
        assert analysis.resolve_hbm_budget() == 2 << 20
        assert analysis.resolve_hbm_budget("1M") == 1 << 20  # explicit wins
        monkeypatch.setenv("PADDLE_HBM_BUDGET", "off")
        assert analysis.resolve_hbm_budget() is None


# ------------------------------------------------------- golden fixtures


def _fixture_donated_update(p, x):
    return p - 0.1 * x.sum(), x * 2


class TestPlanGoldenFixtures:
    """Exact-byte assertions on minimal programs — the 8MiB
    baked-const precedent applied to the liveness scan."""

    def test_donation_credited_at_last_use(self):
        p = jnp.zeros((256, 256), jnp.float32)   # 262144 B
        x = jnp.ones((64, 64), jnp.float32)      # 16384 B
        don = analysis.audit(_fixture_donated_update, p, x, donate=(0,))
        und = analysis.audit(_fixture_donated_update, p, x)
        # undonated: old p + new p coexist — exactly one extra buffer
        assert und.memory.peak_bytes - don.memory.peak_bytes == 262144
        assert don.memory.arg_bytes == [262144, 16384]
        assert don.memory.donated_bytes == 262144
        # the peak live set names the buffers with provenance
        assert don.memory.top[0]["nbytes"] == 262144
        assert any("test_memory_plan.py" in t["source"]
                   for t in don.memory.top if t["source"])

    def test_consts_resident_whole_program(self):
        big = np.ones((512, 512), np.float32)    # 1 MiB baked const

        def prog(x):
            return x @ jnp.asarray(big)

        rep = analysis.audit(prog, jnp.ones((4, 512)),
                             const_budget_bytes=4 << 20)
        assert rep.memory.consts_bytes == 512 * 512 * 4
        assert rep.memory.phases["consts"] == 512 * 512 * 4

    def test_int8_cache_with_bf16_sidecars_exact_bytes(self):
        """The quant-geometry golden fixture: int8 K/V pages with
        per-(position, head) bf16 scale sidecars. Itemsize-based byte
        math must hold exactly, and the (shape, dtype) donation
        pairing must keep the int8 values and the bf16 sidecars in
        SEPARATE slots — a sidecar can never be credited against a
        value buffer."""
        L, B, T, H, D = 2, 2, 32, 2, 8
        kv_bytes = L * B * T * H * D * 1          # int8: 1 B/elem
        sc_bytes = L * B * T * H * 2              # bf16: 2 B/elem

        def update(k, v, ks, vs, nk, nv):
            k = k.at[:, :, 0].set(nk)
            v = v.at[:, :, 0].set(nv)
            ks = ks.at[:, :, 0].set(jnp.bfloat16(1.0))
            vs = vs.at[:, :, 0].set(jnp.bfloat16(1.0))
            return k, v, ks, vs

        sds = jax.ShapeDtypeStruct
        args = (sds((L, B, T, H, D), jnp.int8),
                sds((L, B, T, H, D), jnp.int8),
                sds((L, B, T, H), jnp.bfloat16),
                sds((L, B, T, H), jnp.bfloat16),
                sds((L, B, H, D), jnp.int8),
                sds((L, B, H, D), jnp.int8))
        und = analysis.audit(update, *args,
                             checks=("donation", "memory"),
                             min_donation_bytes=64)
        misses = und.by_check("donation.miss")
        assert sorted(f.data["bytes"] for f in misses) == \
            sorted([kv_bytes, kv_bytes, sc_bytes, sc_bytes])
        # per-operand byte totals are pure itemsize arithmetic
        assert und.memory.arg_bytes == [
            kv_bytes, kv_bytes, sc_bytes, sc_bytes,
            L * B * H * D, L * B * H * D]
        # donating everything repairs coverage AND halves the peak's
        # cache contribution (in-place update, no second copy)
        don = analysis.audit(update, *args, donate=(0, 1, 2, 3),
                             checks=("donation", "memory"),
                             min_donation_bytes=64)
        assert don.donation_coverage == 1.0
        assert und.memory.peak_bytes - don.memory.peak_bytes == \
            2 * kv_bytes + 2 * sc_bytes

    def test_repeated_inlined_subjaxpr_buffers_stay_distinct(self):
        """jax caches traced sub-jaxprs, so two call equations of the
        same jitted subfunction share Var OBJECTS — the scan must
        scope each invocation or it under-counts (an optimistic plan
        is the one failure mode a budget gate cannot have)."""
        g = jax.jit(lambda x: x + 1.0)

        def prog(x):
            return g(x), g(x)

        nb = 256 * 256 * 4
        rep = analysis.audit(prog, jnp.zeros((256, 256), jnp.float32),
                             checks=("memory",))
        assert rep.memory.out_bytes == 2 * nb
        # input + both (distinct) outputs resident at exit
        assert rep.memory.peak_bytes >= 3 * nb

    def test_repeated_subjaxpr_consts_counted_once(self):
        """The flip side of invocation scoping: a cached sub-jaxpr's
        BAKED consts exist once in the executable however many call
        sites reuse it — double-counting would raise false mem.budget
        ERRORs on programs reusing a jitted block with weights."""
        big = np.ones((512, 512), np.float32)            # 1 MiB
        g = jax.jit(lambda x: x @ jnp.asarray(big))

        def prog(x):
            return g(x), g(x) + 1.0

        rep = analysis.audit(prog, jnp.zeros((4, 512), jnp.float32),
                             checks=("memory",))
        assert rep.memory.consts_bytes == 512 * 512 * 4  # once, not 2x

    def test_int4_packed_weight_operand_exact_bytes(self):
        """int4 weights travel as two-nibbles-per-int8: the plan must
        count the PACKED bytes (in/2 x out x 1B), not the logical
        in x out."""
        IN, OUT = 64, 32

        def matmul(wp, scale, x):
            w = wp.astype(jnp.float32) * scale    # stands in for unpack
            return x @ w

        sds = jax.ShapeDtypeStruct
        rep = analysis.audit(
            matmul, sds((IN // 2, OUT), jnp.int8),
            sds((OUT,), jnp.float32), sds((4, IN // 2), jnp.float32),
            checks=("memory",))
        assert rep.memory.arg_bytes[0] == (IN // 2) * OUT * 1
        assert rep.memory.arg_bytes[1] == OUT * 4


# ----------------------------------------------------------- budget gate


class TestBudgetGate:
    def test_audit_kwarg_over_budget_is_error(self):
        p = jnp.zeros((256, 256), jnp.float32)
        x = jnp.ones((64, 64), jnp.float32)
        rep = analysis.audit(_fixture_donated_update, p, x,
                             hbm_budget=1024)
        hits = rep.by_check("mem.budget")
        assert hits and hits[0].severity == Severity.ERROR
        assert hits[0].data["budget_bytes"] == 1024
        assert hits[0].data["over_bytes"] == \
            rep.memory.peak_bytes - 1024
        with pytest.raises(analysis.AuditError, match="mem.budget"):
            rep.raise_on_error()
        # a budget above the peak passes and reports headroom
        ok = analysis.audit(_fixture_donated_update, p, x,
                            hbm_budget="1MiB")
        assert not ok.by_check("mem.budget")
        assert ok.memory.headroom_bytes == \
            (1 << 20) - ok.memory.peak_bytes

    def test_env_budget_gates_every_audit(self, monkeypatch):
        monkeypatch.setenv("PADDLE_HBM_BUDGET", "1KiB")
        rep = analysis.audit(_fixture_donated_update,
                             jnp.zeros((64, 64)), jnp.ones((8, 8)))
        assert rep.by_check("mem.budget")
        monkeypatch.setenv("PADDLE_HBM_BUDGET", "nonsense")
        rep = analysis.audit(_fixture_donated_update,
                             jnp.zeros((64, 64)), jnp.ones((8, 8)))
        bad = rep.by_check("mem.budget_invalid")
        assert bad and bad[0].severity == Severity.WARNING
        assert not rep.by_check("mem.budget")  # NOT silently enforced

    def test_undonated_cache_regression_is_caught(self):
        """THE seeded regression: dropping the decode program's cache
        donation grows the predicted peak by one full cache copy, and
        a budget sized between the two plans turns exactly that drop
        into an AuditError — the gate is not vacuous."""
        from paddle_tpu.generation.api import GenerationSession
        model = _tiny_gpt()
        sess = GenerationSession(model)
        _, donated = sess.audit(2, 16, 128)
        _, undonated = sess.audit(2, 16, 128, donate=())
        cache_bytes = _bytes_of(
            jax.tree_util.tree_leaves(donated.out_shape)[1:-1])
        grown = undonated.memory.peak_bytes - donated.memory.peak_bytes
        # the regression costs at least one K or V cache copy
        assert grown >= cache_bytes // 2
        budget = donated.memory.peak_bytes + grown // 2
        _, ok = sess.audit(2, 16, 128, hbm_budget=budget)
        ok.raise_on_error()
        with pytest.raises(analysis.AuditError, match="mem.budget"):
            sess.audit(2, 16, 128, donate=(),
                       hbm_budget=budget)[1].raise_on_error()

    def test_peak_gauge_and_violation_counter(self):
        metrics.enable()
        analysis.audit(_fixture_donated_update, jnp.zeros((64, 64)),
                       jnp.ones((8, 8)), hbm_budget=1024,
                       name="fixture")
        snap = metrics.snapshot()
        assert snap["analysis.mem.peak_bytes{program=fixture}"][
            "value"] > 1024
        assert snap["analysis.mem.budget_violations{program=fixture}"][
            "value"] == 1


# ------------------------------------------------- flagship plan threading


class TestFlagshipPlans:
    """Every flagship .audit() now carries a MemoryPlan whose floor is
    the program's own resident state — the audit-site threading gate."""

    def test_train_step_plan_covers_params_and_opt(self):
        model = _tiny_gpt()
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        from paddle_tpu.jit.api import TrainStep
        step = TrainStep(model, opt,
                         lambda out, lbl: model.loss(out, lbl))
        ids = np.zeros((2, 16), np.int32)
        rep = step.audit(paddle.to_tensor(ids),
                         paddle.to_tensor(ids.astype(np.int64)))
        params_bytes = sum(_bytes_of(p._data)
                           for p in model.parameters())
        assert rep.memory is not None and rep.memory_checked
        # params (arg 0) exactly; peak holds params + adam moments
        assert rep.memory.arg_bytes[0] == params_bytes
        assert rep.memory.peak_bytes >= 3 * params_bytes

    def test_engine_audit_reports_all_carry_plans(self):
        eng = _tiny_engine()
        reports = eng.audit()
        for key, rep in reports.items():
            assert rep.memory is not None, key
            assert rep.memory.peak_bytes > 0, key
        # decode resident floor: weights + kv cache
        mp = eng.memory_plan()
        assert reports["decode"].memory.peak_bytes >= \
            mp["weights_bytes"] + mp["kv_cache_bytes"]


def _tiny_engine(warmup=False, **serving_kw):
    from paddle_tpu.inference import Config
    from paddle_tpu.serving import ServingEngine
    model = _tiny_gpt()
    spec = [paddle.to_tensor(np.zeros((2, 32), np.int32))]
    cfg = (Config().from_layer(model, spec)
           .enable_generation(max_new_tokens=8,
                              prefill_buckets=(16, 32), max_batch=2,
                              eos_token_id=None)
           .enable_serving(max_queue=8, **serving_kw))
    return ServingEngine(cfg, warmup=warmup)


# --------------------------------------------------- engine budget gate


class TestEngineBudget:
    def test_fail_fast_on_impossible_budget(self):
        with pytest.raises(ValueError, match="predicted peak HBM"):
            _tiny_engine(hbm_budget=100_000)

    def test_health_reports_headroom(self):
        eng = _tiny_engine(hbm_budget="1GiB")
        h = eng.health()
        assert h["hbm_budget"] == 1 << 30
        assert h["predicted_peak_bytes"] > 0
        assert h["predicted_headroom_bytes"] == \
            (1 << 30) - h["predicted_peak_bytes"]

    def test_memory_plan_breakdown_exact(self):
        eng = _tiny_engine()
        mp = eng.memory_plan()
        assert mp["kv_cache_bytes"] == _bytes_of(eng._cache)
        assert mp["weights_bytes"] == _bytes_of(eng._state)
        assert mp["predicted_peak_bytes"] >= mp["decode_peak_bytes"]
        # plan surfaces in health() once computed
        assert eng.health()["predicted_peak_bytes"] == \
            mp["predicted_peak_bytes"]

    def test_int8_engine_plans_smaller_cache(self):
        wide = _tiny_engine().memory_plan()
        quant = _tiny_engine(
            kv_cache_dtype="int8").memory_plan()
        # int8 values + bf16 sidecars < fp32 values (the quant
        # geometry flows through the planner end to end)
        assert quant["kv_cache_bytes"] < wide["kv_cache_bytes"]
        assert quant["predicted_peak_bytes"] < \
            wide["predicted_peak_bytes"]

    def test_garbage_env_budget_swallowed_observably(self, monkeypatch):
        monkeypatch.setenv("PADDLE_HBM_BUDGET", "garbage")
        metrics.enable()
        eng = _tiny_engine()   # must not raise
        assert eng.hbm_budget is None
        snap = metrics.snapshot()
        assert any(k.startswith("errors.swallowed") for k in snap)

    def test_garbage_explicit_budget_raises(self):
        """An operator who ASKED for a gate must get one: explicit
        garbage raises instead of silently serving ungated."""
        with pytest.raises(ValueError, match="unparseable byte size"):
            _tiny_engine(hbm_budget="16 gigs")


# ------------------------------------------------- predicted vs measured


class TestPredictedVsMeasured:
    """The plan against live-byte deltas from device.max_memory_
    allocated() on CPU: never below the resident set, within the
    documented _SLACK above it."""

    def _measure(self, fn, args, held):
        """(resident_bytes, outs): inputs in ``held`` stay referenced
        across the dispatch; resident = held bytes + the live-byte
        growth the outputs caused."""
        device.reset_peak_memory_stats()
        m0 = device.memory_allocated()
        outs = fn(*args)
        jax.block_until_ready(outs)
        m1 = device.max_memory_allocated()
        return _bytes_of(held) + max(0, m1 - m0), outs

    def test_decode_program_within_slack(self):
        from paddle_tpu.generation.api import (GenerationConfig,
                                               GenerationSession)
        model = _tiny_gpt()
        sess = GenerationSession(model)
        cfg = GenerationConfig()
        state = sess.state_values()
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, (2, 16)),
            jnp.int32)
        plen = jnp.full((2,), 16, jnp.int32)
        key = jax.random.PRNGKey(0)
        tok, cache, key2, fin = sess.prefill(state, ids, plen, key,
                                             cfg, 128)
        jax.block_until_ready(tok)
        # CPU dispatch donates nothing: plan the same undonated program
        plan = analysis.plan_memory(
            sess._decode_fn, state, tok, cache, key2, fin, cfg,
            static_argnums=(5,), name="decode.measured")
        measured, _ = self._measure(
            lambda *a: sess.decode(*a, cfg),
            (state, tok, cache, key2, fin),
            (state, tok, cache, key2, fin))
        assert measured <= plan.peak_bytes <= _SLACK * measured, \
            (measured, plan.peak_bytes)

    def test_engine_decode_program_within_slack(self):
        eng = _tiny_engine()
        # the step's record, with nothing donated: CPU dispatch donates
        # nothing, so plan the same undonated program
        step = dataclasses.replace(eng._programs[("step",)], donates=())
        args = (eng._state, eng._cache, eng._lanes, eng._key)
        plan = step.plan("engine.decode.measured")
        measured, _ = self._measure(
            lambda *a: step.jit(*a, eng._cfg), args, args)
        assert measured <= plan.peak_bytes <= _SLACK * measured, \
            (measured, plan.peak_bytes)


# ----------------------------------------------------------- the ledger


class TestProgramLedger:
    """The committed docs/programs.json drift gate (the docs/metrics.md
    precedent): a PR that silently drops a donation, bakes a constant,
    or grows any flagship program's peak HBM fails HERE with a diff
    naming the program and the field."""

    def test_manifest_current_and_update_byte_stable(self, monkeypatch):
        from paddle_tpu.analysis import ledger
        # hermetic: a developer's exported knobs must not alter the
        # regenerated programs (tools/ledger scrubs these the same way)
        for knob in ledger.SCRUB_ENV:
            monkeypatch.delenv(knob, raising=False)
        fresh = ledger.build_ledger()          # trace-only, built once
        diffs = ledger.check(fresh=fresh)
        assert not diffs, \
            "docs/programs.json drift (run `python -m tools.ledger " \
            "--update` if deliberate):\n  " + "\n  ".join(diffs)
        # --update on an unchanged tree is byte-stable: regenerated
        # text == the committed file, byte for byte
        with open(ledger.ledger_path(), "r", encoding="utf-8") as f:
            assert ledger.render(fresh) == f.read()

    def test_entry_fields_are_plain_data(self):
        """Ledger rows hold only JSON-stable scalars — every field
        round-trips json.dumps bit-exactly (floats pre-rounded)."""
        import json

        from paddle_tpu.analysis import ledger
        rep = analysis.audit(_fixture_donated_update,
                             jnp.zeros((64, 64)), jnp.ones((8, 8)),
                             donate=(0,))
        entry = ledger.entry_for(rep)
        assert entry["peak_bytes"] == rep.memory.peak_bytes
        assert entry["fingerprint"] == rep.fingerprint
        assert json.loads(json.dumps(entry)) == entry

    def test_fingerprint_tracks_structure_not_values(self):
        """Same shapes/program -> same fingerprint; a donation change
        or a shape change re-fingerprints (the drift key is
        structural)."""
        a = analysis.audit(_fixture_donated_update,
                           jnp.zeros((64, 64)), jnp.ones((8, 8)))
        b = analysis.audit(_fixture_donated_update,
                           jnp.full((64, 64), 3.0), jnp.ones((8, 8)))
        assert a.fingerprint == b.fingerprint
        c = analysis.audit(_fixture_donated_update,
                           jnp.zeros((64, 64)), jnp.ones((8, 8)),
                           donate=(0,))
        d = analysis.audit(_fixture_donated_update,
                           jnp.zeros((32, 32)), jnp.ones((8, 8)))
        assert len({a.fingerprint, c.fingerprint, d.fingerprint}) == 3


# ------------------------------------------------------ runtime crosscheck


class TestCrossCheckMemory:
    def test_refuses_unchecked_report(self):
        rep = analysis.audit(_fixture_donated_update,
                             jnp.zeros((8, 8)), jnp.ones((4, 4)),
                             checks=("host_sync",))
        assert not rep.memory_checked
        with pytest.raises(ValueError, match="without the 'memory'"):
            analysis.cross_check_memory(rep, measured_bytes=1)

    def test_flags_underestimate_only(self):
        rep = analysis.audit(_fixture_donated_update,
                             jnp.zeros((8, 8)), jnp.ones((4, 4)))
        peak = rep.memory.peak_bytes
        ok = analysis.cross_check_memory(rep, measured_bytes=peak)
        assert not ok.by_check("mem.underestimate")
        bad = analysis.cross_check_memory(rep,
                                          measured_bytes=peak * 10)
        hits = bad.by_check("mem.underestimate")
        assert hits and hits[0].severity == Severity.WARNING
        assert hits[0].data == {"measured": peak * 10,
                                "predicted": peak}

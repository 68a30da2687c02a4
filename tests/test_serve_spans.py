"""Spans inside ``engine.step()``, set-up and ``TrainStep`` (ISSUE 26):
one recorder, one clock. On a tiny engine: every child span lies inside
its parent; a request's ``serve.queue_wait`` + ``serve.prefill`` is its
time to first token, from the same stamps as ``admitted_at`` /
``first_token_at``; the polls' ``emitted`` counts add up to what the
requests emitted; a compile after warm-up shows as one ``jit.program``
under the scheduler iteration that hit it."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder as fr
from paddle_tpu.serving import RequestParams


@pytest.fixture(autouse=True)
def _fresh_recorder():
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    yield
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)


def _engine(**kw):
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
    gen = dict(max_new_tokens=8, prefill_buckets=(8, 16), max_batch=2)
    gen.update(kw.pop("generation", {}))
    cfg = Config().from_layer(m, spec).enable_generation(**gen)
    if "serving" in kw:
        cfg = cfg.enable_serving(**kw.pop("serving"))
    return ServingEngine(cfg, **kw)


def _spans():
    return fr.spans_between(0, 2 ** 62)


def _named(name, **where):
    return [s for s in _spans() if s.name == name and all(
        s.fields.get(k) == v for k, v in where.items())]


def _drain(eng, prompts, budgets=None):
    """Submit, pump until idle; returns the handles."""
    budgets = budgets or [None] * len(prompts)
    handles = [eng.submit(p, RequestParams(max_new_tokens=b))
               for p, b in zip(prompts, budgets)]
    while eng.busy:
        eng.step()
    return handles


PROMPTS = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 12, 3, 9, 7)]
BUDGETS = [8, 3, 1, 6, 8]

ENGINES = [
    pytest.param({}, id="dense"),
    pytest.param({"serving": dict(paged=True, kv_page_size=8)},
                 id="paged"),
    pytest.param({"generation": dict(prefill_buckets=(16,)),
                  "serving": dict(prefill_chunk_tokens=4)},
                 id="chunked"),
]


@pytest.fixture(params=ENGINES)
def drained(request):
    """(engine, handles, spans) of one drained run of PROMPTS."""
    eng = _engine(**request.param, poll_every=2)
    fr.clear()      # keep the run, drop set-up
    handles = _drain(eng, PROMPTS, BUDGETS)
    spans = _spans()
    yield eng, handles, spans
    eng.shutdown()


def test_every_child_lies_inside_its_parent(drained):
    _, _, spans = drained
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)            # ids are unique
    children = [s for s in spans if s.parent is not None]
    assert children
    for s in children:
        p = by_id[s.parent]
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns, (s, p)
    # the tree the table in ISSUE 26 draws; since ISSUE 31 the wait for
    # a prefill's token follows the iteration's decode dispatch, outside
    # its serve.admit
    tree = {(s.name, by_id[s.parent].name) for s in children}
    assert {("serve.admit", "serve.step"),
            ("serve.dispatch", "serve.step"),
            ("serve.poll", "serve.step"),
            ("serve.sync", "serve.step"),
            ("serve.sync", "serve.poll")} <= tree
    assert ("serve.sync", "serve.admit") not in tree
    assert all(s.parent is None for s in spans if s.name == "serve.step")
    # request spans carry their trace id, iteration spans carry none
    for s in spans:
        if s.name in ("serve.queue_wait", "serve.prefill"):
            assert s.trace and s.parent is None
        elif s.name.startswith("serve."):
            assert s.trace is None


def test_queue_wait_plus_prefill_is_time_to_first_token(drained):
    _, handles, spans = drained
    for h in handles:
        (qw,) = [s for s in spans if s.name == "serve.queue_wait"
                 and s.fields["req"] == h.id]
        (pf,) = [s for s in spans if s.name == "serve.prefill"
                 and s.fields["req"] == h.id]
        assert qw.trace == pf.trace == h.trace_id
        assert qw.end_ns == pf.start_ns            # one stamp
        assert h.submitted_at <= h.admitted_at < h.first_token_at
        ttft_ns = (h.first_token_at - h.submitted_at) * 1e9
        got = (qw.end_ns - qw.start_ns) + (pf.end_ns - pf.start_ns)
        assert abs(got - ttft_ns) < 1e6            # 1 ms
        # admitted_at / first_token_at ARE the spans' stamps
        assert abs(h.admitted_at * 1e9 - qw.end_ns) < 1e3
        assert abs(h.first_token_at * 1e9 - pf.end_ns) < 1e3
        # the prefill's sync closes the prefill span, after its admit
        (sync,) = [s for s in spans if s.name == "serve.sync"
                   and s.fields["site"] == "prefill"
                   and s.end_ns == pf.end_ns]
        (admit,) = [s for s in spans if s.name == "serve.admit"
                    and s.fields["req"] == h.id]
        if "chunks" not in admit.fields:
            assert admit.end_ns <= sync.start_ns
    admits = [s for s in spans if s.name == "serve.admit"]
    assert sorted(s.fields["req"] for s in admits) == \
        sorted(h.id for h in handles)
    for s in admits:
        assert {"slot", "bucket", "prompt"} <= set(s.fields)


def test_poll_emitted_adds_up_to_what_the_requests_emitted(drained):
    eng, handles, spans = drained
    polls = [s for s in spans if s.name == "serve.poll"]
    want = sum(h.n_emitted for h in handles)
    assert want == sum(BUDGETS)
    assert sum(s.fields["emitted"] for s in polls) == want
    assert eng.stats["emitted_tokens"] == want
    assert eng.stats["polls"] == len(polls)
    assert sum(s.fields["admitted"] for s in polls) == len(handles)
    assert sum(s.fields["completed"] for s in polls) == len(handles)
    # decode tokens = emitted less the prefills' first tokens, and no
    # poll covers more lane-steps than were dispatched
    steps = [s for s in spans if s.name == "serve.step"]
    dispatched = sum(s.fields["decode"] for s in steps)
    assert sum(s.fields["steps"] for s in polls) == dispatched
    # every program's call is a serve.dispatch (ISSUE 36): the decode
    # steps are those of program "step"
    assert len([s for s in spans if s.name == "serve.dispatch"
                and s.fields["program"] == "step"]) \
        == dispatched == eng.stats["decode_steps"]
    decoded = want - len(handles)
    assert 0 < decoded <= dispatched * eng.max_batch
    # what a sync waits behind: the decode steps dispatched before the
    # program it waits for that no earlier sync saw land. A poll's read
    # never waits behind more than the steps the poll covers, and the
    # step a full engine dispatches ahead of the read is not among them
    by_id = {s.id: s for s in spans}
    poll_syncs = [s for s in spans if s.name == "serve.sync"
                  and s.fields["site"] == "poll"]
    assert len(poll_syncs) == len(polls)
    for s in poll_syncs:
        assert 0 <= s.fields["steps_queued"] \
            <= by_id[s.parent].fields["steps"] <= eng.poll_every
    # one read of the result rows a poll that completed a lane, behind
    # the same program as the poll's own read: nothing more to wait for
    rows = [s for s in spans if s.name == "serve.sync"
            and s.fields["site"] == "row"]
    assert [by_id[s.parent].id for s in rows] == \
        [p.id for p in polls if p.fields["completed"]]
    assert all(s.fields["steps_queued"] == 0 for s in rows)
    assert sum(s.fields["steps_queued"] for s in spans
               if s.name == "serve.sync") == dispatched


def test_progress_has_a_public_reader():
    """``n_emitted`` is brought up to date at every poll, before the
    request finishes."""
    eng = _engine(poll_every=1)
    h = eng.submit(PROMPTS[0], RequestParams(max_new_tokens=8))
    seen = []
    while not h.done():
        eng.step()
        seen.append(h.n_emitted)
    assert seen == sorted(seen) and seen[0] >= 1 and seen[-1] == 8
    assert len(set(seen)) > 2          # it moved between polls
    assert eng.stats["emitted_tokens"] == 8
    eng.shutdown()


def test_never_admitted_request_records_its_queue_wait():
    eng = _engine()
    fr.clear()
    h = eng.submit(PROMPTS[0])
    eng.drain()                        # rejected while still queued
    (qw,) = _named("serve.queue_wait", req=h.id)
    assert qw.fields["status"] == "rejected"
    assert h.admitted_at is None and not _named("serve.prefill")
    assert abs(qw.end_ns - h.finished_at * 1e9) < 1e3


@pytest.mark.parametrize("kw", ENGINES)
def test_failed_admission_left_the_queue_without_a_prefill(kw):
    """``admitted_at`` means "left the queue": a request whose prefill
    raises has it set, a ``serve.queue_wait`` that ends there, no
    ``serve.prefill`` and no first token, and goes CANCELLED."""
    from paddle_tpu.serving import RequestStatus
    eng = _engine(**kw)
    fr.clear()

    def boom(*a):
        raise RuntimeError("injected")
    # the program fetch of either admission path, before any dispatch
    eng._compiled = boom
    h = eng.submit(PROMPTS[1])
    while not h.done():
        eng.step()
    assert h.status is RequestStatus.CANCELLED
    assert "admission error" in h.detail
    (qw,) = _named("serve.queue_wait", req=h.id)
    assert abs(h.admitted_at * 1e9 - qw.end_ns) < 1e3
    assert h.first_token_at is None and not _named("serve.prefill")
    (admit,) = _named("serve.admit", req=h.id)
    assert admit.start_ns == qw.end_ns
    eng.shutdown()


def test_setup_spans_and_a_compile_after_warmup():
    """Set-up is timed by span; a bucket that slipped past warm-up shows
    as one ``jit.program{source=compile}`` under the ``serve.step``
    that hit it."""
    fr.clear()
    eng = _engine(warmup=False)
    (init,) = _named("setup.engine_init")
    for name in ("setup.state", "setup.cache_alloc"):
        (s,) = _named(name)
        assert s.parent == init.id
    (alloc,) = _named("setup.cache_alloc")
    assert alloc.fields["bytes"] > 0
    assert not _named("jit.program") and not _named("setup.warmup")
    # warm everything but the 16 bucket
    for key in eng._programs:
        if key != ("prefill", 16):
            eng._compiled(key)
    eng._warm = True
    warm = _named("jit.program")
    assert sorted(s.fields["label"] for s in warm) == [
        "serving.admit", "serving.free", "serving.poll_view",
        "serving.prefill.8", "serving.step"]
    assert all(s.fields["source"] in ("store", "persistent_cache",
                                      "compile")
               and s.fields["lower_s"] >= 0 for s in warm)
    fr.clear()
    (h,) = _drain(eng, [PROMPTS[1]], [2])      # 12 tokens: bucket 16
    assert h.n_emitted == 2
    (prog,) = _named("jit.program")
    assert prog.fields["label"] == "serving.prefill.16"
    assert prog.fields["source"] in ("compile", "persistent_cache")
    by_id = {s.id: s for s in _spans()}
    admit = by_id[prog.parent]
    assert admit.name == "serve.admit" and admit.fields["req"] == h.id
    assert by_id[admit.parent].name == "serve.step"
    compiles = [f for _, k, f in fr.events() if k == "jit.compile"]
    assert [f["cause"] for f in compiles] == ["new_shape"]
    eng.shutdown()


def test_warmup_is_one_span_over_its_programs():
    fr.clear()
    eng = _engine()
    (init,) = _named("setup.engine_init")
    (warm,) = _named("setup.warmup")
    assert warm.parent == init.id
    progs = _named("jit.program")
    assert len(progs) == len(eng._exes) == 6
    assert all(p.parent == warm.id for p in progs)
    eng.shutdown()


STEPS = [pytest.param("TrainStep", id="TrainStep"),
         pytest.param("DistributedTrainStep", id="fleet")]


@pytest.mark.parametrize("kind", STEPS)
def test_train_step_span(kind):
    """One ``train.step`` per call, ``compiled=1`` and a ``jit.program``
    child on the call that built the program, none after."""
    from paddle_tpu import nn, optimizer
    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1,
                        parameters=model.parameters())
    loss_fn = lambda o, y: ((o - y) ** 2).mean()  # noqa: E731
    if kind == "TrainStep":
        step = paddle.jit.TrainStep(model, opt, loss_fn)
    else:
        from paddle_tpu.distributed import fleet
        fleet.init(is_collective=True)
        step = fleet.DistributedTrainStep(model, opt, loss_fn)
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    fr.clear()
    for _ in range(3):
        step(x, y)
    steps = _named("train.step")
    assert len(steps) == 3 and all(s.parent is None for s in steps)
    assert [s.fields.get("compiled", 0) for s in steps] == [1, 0, 0]
    (prog,) = _named("jit.program")
    assert prog.parent == steps[0].id
    assert prog.fields["source"] in ("compile", "persistent_cache")
    assert steps[0].start_ns <= prog.start_ns <= prog.end_ns \
        <= steps[0].end_ns

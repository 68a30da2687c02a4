"""The scheduler blocks on the device only with work queued behind what
it waits for (ISSUE 31): an iteration dispatches its admissions and its
decode step before it waits for the prefills' tokens, and a poll of an
engine that could admit nothing anyway (every slot full, or the queue's
head blocked for pages) dispatches the next step before it reads the
lanes as the step before left them.

Held here, on the CPU: every request's tokens stay the sequential greedy
ones in all three step modes, full or not; ``serve.sync``'s ``ahead``
says when the mechanism engages and that a poll is never more than one
step ahead; an admission that fails at the deferred wait, or at the admit
program, goes terminal with its pages and slot returned. CPU runs donate
nothing, so a read of a donated buffer cannot show here:
``chip_smoke.py``'s serve phases and the benchmark's ``correct`` hold
that on the chip.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder as fr
from paddle_tpu.inference import Config
from paddle_tpu.serving import RequestParams, RequestStatus, ServingEngine

BLOCK = dict(block_length=4, denoising_steps=2, mask_token_id=95)
MODES = {"decode": {}, "speculative": {"speculative": "ngram"},
         "block_diffusion": {"block_diffusion": BLOCK}}
# what stands behind the poll's read, by the engine's shape: 7 requests
# through 2 slots keep every slot full; 8 slots always leave one free;
# 4 slots over 6 pages leave slots free and the queue's head blocked
SHAPES = {"full": dict(max_batch=2),
          "free": dict(max_batch=8),
          "page_blocked": dict(max_batch=4, kv_pages=7)}
# (prompt tokens, budget): prompts on both buckets, budgets from one
# token (finished at its prefill) to the cap, none shorter than a block
JOBS = [(5, 8), (12, 3), (4, 1), (20, 6), (7, 8), (30, 5), (9, 2)]


@pytest.fixture(autouse=True)
def _fresh_recorder():
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    yield
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)


def _model(mode):
    paddle.seed(0)
    if mode != "block_diffusion":
        from paddle_tpu.models.gpt import gpt
        return gpt("test-tiny")
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    return SDARForCausalLM(SDARConfig(
        dtype="float32", vocab_size=96, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
        rope_theta=1e6, max_position_embeddings=256))


_ENGINES = {}


def _engine(mode, shape):
    """One warm, idle engine a (mode, shape), kept for the module."""
    if (mode, shape) not in _ENGINES:
        model = _model(mode)
        model.eval()
        kw = dict(SHAPES[shape])
        cfg = (Config()
               .from_layer(model,
                           [paddle.to_tensor(np.zeros((1, 16), np.int32))])
               .enable_generation(max_new_tokens=8, prefill_buckets=(16, 32),
                                  max_batch=kw.pop("max_batch"),
                                  eos_token_id=None, **MODES[mode])
               .enable_serving(max_queue=16, paged=True, kv_page_size=16,
                               **kw))
        _ENGINES[mode, shape] = ServingEngine(cfg)
    return _ENGINES[mode, shape]


@pytest.fixture(scope="module", autouse=True)
def _shutdown_engines():
    yield
    for eng in _ENGINES.values():
        eng.shutdown()
    _ENGINES.clear()


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 90, n).astype(np.int32) for n, _ in JOBS]


def _pump(eng, limit=400):
    """Step until idle; per iteration, whether every slot was full once
    its admissions were in (what the iteration's poll decides on)."""
    full = []
    while eng.busy:
        free = sum(s is None for s in eng._slots)
        full.append(len(eng._queue) >= free)
        eng.step()
        assert len(full) < limit, "the engine does not drain"
    return full


_SEQUENTIAL = {}


def _sequential(mode):
    """Every job's tokens, served ALONE by an engine with slots free
    (nothing ever runs ahead there) at a poll every step: the tree's
    sequential greedy result. Speculation's is plain decode's."""
    ref = "block_diffusion" if mode == "block_diffusion" else "decode"
    if ref not in _SEQUENTIAL:
        eng = _engine(ref, "free")
        eng.poll_every = 1
        out = []
        for prompt, (_, budget) in zip(_prompts(), JOBS):
            h = eng.submit(prompt, RequestParams(max_new_tokens=budget))
            _pump(eng)
            out.append(h.result())
        _SEQUENTIAL[ref] = out
    return _SEQUENTIAL[ref]


def _children(spans, parent, name, **where):
    return [s for s in spans if s.parent == parent.id and s.name == name
            and all(s.fields.get(k) == v for k, v in where.items())]


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("poll_every", [1, 4])
@pytest.mark.parametrize("mode", list(MODES))
def test_tokens_are_the_sequential_greedy_ones(mode, poll_every, shape):
    want = _sequential(mode)
    eng = _engine(mode, shape)
    eng.poll_every = poll_every
    before = dict(eng.stats)
    fr.clear()
    handles = [eng.submit(p, RequestParams(max_new_tokens=b))
               for p, (_, b) in zip(_prompts(), JOBS)]
    _pump(eng)
    for h, ref in zip(handles, want):
        assert h.status is RequestStatus.COMPLETED
        np.testing.assert_array_equal(h.result(), ref)     # bitwise
    # each finished lane completes exactly once; nothing is left held
    spans = fr.spans_between(0, 2 ** 62)
    polls = [s for s in spans if s.name == "serve.poll"]
    assert sum(s.fields["completed"] for s in polls) == len(JOBS) \
        == eng.stats["completed"] - before["completed"]
    assert eng.stats["emitted_tokens"] - before["emitted_tokens"] \
        == sum(b for _, b in JOBS)
    assert all(s is None for s in eng._slots) and not eng._pending_pages
    assert eng._alloc.used_pages() == 0
    assert eng._alloc.free_pages() == eng._alloc.n_pages - 1
    eng._alloc.assert_conserved()
    # the shape stands behind the polls' reads as it says
    by_id = {s.id: s for s in spans}
    ahead = [(s.fields["ahead"], by_id[by_id[s.parent].parent].fields["live"])
             for s in spans if s.name == "serve.sync"
             and s.fields["site"] == "poll"]
    assert all(a in (0, 1) for a, _ in ahead)
    if shape == "free":
        assert not any(a for a, _ in ahead)
    else:
        assert any(a for a, _ in ahead)
    if shape == "page_blocked":
        # ahead with lanes to spare: the queue's head waited for pages
        assert any(a and live < eng.max_batch for a, live in ahead)


@pytest.mark.parametrize("mode", list(MODES))
def test_ahead_says_what_is_queued_behind_each_read(mode):
    """Per iteration, from the recorder: with every slot full the poll's
    read has exactly one program behind what it waits for, the step, and
    the rows are read from the same copies; with a slot free, nothing;
    a prefill's token is waited for with its admit program (and, the
    iteration's last, the decode step) behind it."""
    eng = _engine(mode, "full")
    eng.poll_every = 2
    fr.clear()
    for p, (_, b) in zip(_prompts(1), JOBS):
        eng.submit(p, RequestParams(max_new_tokens=b))
    full = _pump(eng)
    spans = fr.spans_between(0, 2 ** 62)
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == len(full) and True in full and False in full
    n_ahead = 0
    for step, was_full in zip(steps, full):
        decode = step.fields["decode"]
        polls = _children(spans, step, "serve.poll")
        assert len(polls) <= 1 and 0 <= decode <= 2
        waits = _children(spans, step, "serve.sync", site="prefill")
        for sync in waits:
            # its own admit program, always; the last of an iteration has
            # the decode step too: an earlier one is waited for before
            # the next prefill goes out (two prefill rows alive at most)
            assert sync.fields["ahead"] == \
                (2 if decode and sync is waits[-1] else 1)
        for poll in polls:
            (read,) = _children(spans, poll, "serve.sync", site="poll")
            assert read.fields["ahead"] == int(was_full)
            n_ahead += was_full
            # never more than one step ahead of a poll's read, and the
            # poll covers the steps before that one
            inside = _children(spans, poll, "serve.dispatch",
                               program="step")
            assert len(inside) == int(was_full)
            assert decode == len(_children(spans, step, "serve.dispatch",
                                           program="step")) + len(inside)
            # the copies the read is of, made before the step ahead
            assert len(_children(spans, poll, "serve.dispatch",
                                 program="poll_view")) == int(was_full)
            assert 1 <= poll.fields["steps"] <= eng.poll_every
            rows = _children(spans, poll, "serve.sync", site="row")
            assert len(rows) == (1 if poll.fields["completed"]
                                 + poll.fields["evicted"] else 0)
            for row in rows:
                # behind the same program as the poll's read: it does
                # not wait for the step in flight
                assert row.fields["ahead"] == read.fields["ahead"]
                assert row.fields["steps_queued"] == 0
    assert n_ahead >= 2
    assert sum(s.fields["steps"] for s in spans if s.name == "serve.poll") \
        == sum(s.fields["decode"] for s in steps)


def test_deadline_eviction_at_a_poll_ahead_keeps_what_the_poll_saw():
    """A lane evicted by a poll that ran ahead keeps the tokens of the
    poll's own view: a bitwise prefix of the sequential result, as long
    as the steps the view covers."""
    want = _sequential("decode")
    eng = _engine("decode", "full")
    eng.poll_every = 2
    prompts = _prompts()
    fr.clear()
    a = eng.submit(prompts[0], RequestParams(max_new_tokens=8))
    b = eng.submit(prompts[4], RequestParams(max_new_tokens=8,
                                             deadline_s=3600.0))
    for _ in range(2):
        eng.step()
    assert b.status is RequestStatus.RUNNING
    b.deadline = 0.0            # overdue at the next poll
    _pump(eng)
    assert a.status is RequestStatus.COMPLETED
    np.testing.assert_array_equal(a.result(), want[0])
    assert b.status is RequestStatus.CANCELLED and b.detail == "deadline"
    assert 1 <= b.tokens.size == b.n_emitted < 8
    np.testing.assert_array_equal(b.tokens, want[4][:b.tokens.size])
    (evict,) = [f for _, k, f in fr.events() if k == "serve.evict"]
    assert evict["tokens"] == b.tokens.size
    eng._alloc.assert_conserved()
    assert eng._alloc.used_pages() == 0


@pytest.mark.chaos
@pytest.mark.parametrize("at", ["wait", "admit"])
@pytest.mark.parametrize("mode", ["decode", "block_diffusion"])
def test_admission_that_fails_late_goes_terminal(mode, at):
    """The first admission of two in one iteration fails — at the deferred wait for its
    prefill's token (it already sits in its slot, the decode step is
    queued behind it), or at the admit program (its prefill is
    dispatched, it reached no slot): CANCELLED with ``admission error``,
    its pages and its slot free again, the others served bitwise."""
    # imported here: a module that imports the harness is all chaos tier
    from paddle_tpu.utils import fault_injection as fi
    want = _sequential(mode)
    eng = _engine(mode, "full")
    eng.poll_every = 4
    before = dict(eng.stats)
    fr.clear()
    handles = [eng.submit(p, RequestParams(max_new_tokens=b))
               for p, (_, b) in zip(_prompts(), JOBS)]
    # two slots are free: both admissions are dispatched, then the decode
    # step, and only then is either token waited for
    with fi.fail_admission(eng, n=1, at=at) as fault:
        eng.step()
    fired = fault.triggered
    assert fired == 1
    _pump(eng)
    failed = [h for h in handles if h.status is RequestStatus.CANCELLED]
    assert len(failed) == 1 and "admission error" in failed[0].detail
    assert "injected" in failed[0].detail
    assert eng.stats["cancelled"] - before["cancelled"] == 1
    for h, ref in zip(handles, want):
        if h is not failed[0]:
            assert h.status is RequestStatus.COMPLETED
            np.testing.assert_array_equal(h.result(), ref)
    # left the queue, so admitted_at is set; no first token unless the
    # failure came after the program ran (neither case stamps one)
    assert failed[0].admitted_at is not None
    assert failed[0].first_token_at is None
    assert all(s is None for s in eng._slots) and not eng._pending_pages
    assert eng._alloc.used_pages() == 0
    assert eng._alloc.free_pages() == eng._alloc.n_pages - 1
    eng._alloc.assert_conserved()
    if at == "wait":
        # the slot was taken back by the free program, as an eviction
        (evict,) = [f for _, k, f in fr.events() if k == "serve.evict"]
        assert evict["req"] == failed[0].id
        assert "admission error" in evict["reason"]

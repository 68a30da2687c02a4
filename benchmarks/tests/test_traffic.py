"""The generators: the same seed gives the same inputs, another seed the
same set of sizes in another order with other ids."""
import numpy as np

import common
import traffic


def _mix(name):
    return common.load_json(common.HERE, "traffic", name + ".json")


def test_requests_repeat_and_differ():
    tr = dict(_mix("chat-poisson"), rate_per_s=8.0)
    a = traffic.Requests(tr, 50304, 3_000_000_019)
    b = traffic.Requests(tr, 50304, 3_000_000_019)
    c = traffic.Requests(tr, 50304, 7)
    assert all(np.array_equal(a.prompt(k), b.prompt(k)) for k in range(64))
    # round the pool again: the same size, other ids
    assert a.prompt(len(a)).size == a.prompt(0).size
    assert not np.array_equal(a.prompt(len(a))[:4], a.prompt(0)[:4])
    assert np.array_equal(a.output_len, b.output_len)
    assert np.array_equal(a.due, b.due)
    assert not np.array_equal(a.prompt_len, c.prompt_len)
    assert not np.array_equal(a.prompt(0)[:8], c.prompt(0)[:8])
    # every seed offers the same work: same sizes, same gaps, other order
    assert sorted(a.prompt_len) == sorted(c.prompt_len)
    assert sorted(a.output_len) == sorted(c.output_len)
    assert abs(a.due[-1] - c.due[-1]) < 1e-6 * a.due[-1]
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert lo <= a.prompt_len.min() and a.prompt_len.max() <= hi
    # the published means (the sampler's lower limit adds its 4 tokens)
    assert abs(a.prompt_len.mean() - 165) < 8
    assert a.output_len.max() == tr["output_len"]["cap"]
    assert abs(a.output_len.mean() - 265) < 10
    assert abs(len(a) / a.due[-1] - 8.0) < 0.5


def test_population_in_flight_at_the_start():
    tr = _mix("offline-batch")
    a = traffic.Requests(tr, 50304, 11)
    c = traffic.Requests(tr, 50304, 12)
    assert len(a.start_prompts) == tr["in_flight_at_start"] == 64
    assert sorted(a.start_output_len) == sorted(c.start_output_len)
    assert a.start_output_len.min() >= 1
    assert a.start_prompt_len.max() <= tr["prompt_len"]["max"]
    # met in flight: longer than the average request, and part way through
    assert a.start_output_len.mean() < a.output_len.mean()
    assert a.start_prompt_len.mean() > a.prompt_len.mean()


def test_train_batches_repeat_and_differ():
    tr = _mix("pretrain-b16-s1024")
    a = traffic.train_batches(tr, 50304, 5)
    assert a.shape == (8, 16, 1024) and a.max() < 50304
    assert np.array_equal(a, traffic.train_batches(tr, 50304, 5))
    assert not np.array_equal(a, traffic.train_batches(tr, 50304, 6))
    rows = a.reshape(-1, 1024)
    assert len({r.tobytes() for r in rows}) == len(rows)   # all differ


def test_bursty_gaps_keep_the_rate():
    tr = dict(_mix("chat-poisson"), rate_per_s=8.0,
              arrivals={"dist": "gamma", "cv": 2.5})
    r = traffic.Requests(tr, 50304, 1)
    assert abs(len(r) / r.due[-1] - 8.0) < 1.0

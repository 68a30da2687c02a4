"""The one general traffic generator: reads a traffic file, makes inputs.

A traffic mix is data: ``traffic/<name>.json`` with a ``kind``
(``train_steps``, ``closed_loop``, ``open_loop``, which selects the driver
loop) and parameters.  Sizes and arrival gaps are drawn ONCE from the
file's own ``shape_seed``; ``--seed`` only permutes their order and draws
the token ids, so every seed offers the same set of work in another order
and runs differ by noise, not by luck of the draw.
"""
from __future__ import annotations

import numpy as np


def _draw(spec: dict, n: int, rng) -> np.ndarray:
    """Lengths from a published mean: the exponential is the one
    distribution a mean alone determines (maximum entropy on the positive
    numbers).  A draw outside [min, max] is drawn again, as a sampler that
    prunes too-short and too-long sequences does; ``cap`` then clips, as a
    deployment's limit on new tokens does."""
    if spec["dist"] != "exponential":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec["min"], spec["max"]
    x = np.rint(rng.exponential(spec["mean"], n))
    while True:
        out = (x < lo) | (x > hi)
        if not out.any():
            break
        x[out] = np.rint(rng.exponential(spec["mean"], int(out.sum())))
    return np.minimum(x, spec.get("cap", hi)).astype(np.int64)


def _gaps(spec: dict, rate: float, n: int, rng) -> np.ndarray:
    if spec["dist"] == "exponential":
        return rng.exponential(1.0 / rate, n)
    if spec["dist"] == "gamma":          # bursty: cv > 1
        cv = float(spec["cv"])
        k = 1.0 / (cv * cv)
        return rng.gamma(k, 1.0 / (rate * k), n)
    raise ValueError(f"unknown arrival distribution {spec['dist']!r}")


def train_batches(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """[distinct_batches, batch, seq_len] token ids, every row different."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(
        0, vocab, (traffic["distinct_batches"], traffic["batch"],
                   traffic["seq_len"]), dtype=np.int64).astype(np.int32)


def _ids(n: int, vocab: int, rng) -> np.ndarray:
    return rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)


class Requests:
    """The ordered requests of one run: prompt ids, output budget and, for
    an open loop, the time each is due (seconds from the start).  A run
    that needs more than ``pool_size`` requests goes round the pool again,
    with new ids each time: no two prompts ever share a prefix.  A pool
    about as large as one run's needs gives every seed the same set of
    sizes, whole, in another order.

    ``in_flight_at_start`` requests come first, all due at 0: the
    population a system in steady state holds, so that the window does not
    measure a ramp.  A request met in flight is a long one more often than
    a short one (in proportion to its output) and is met at a uniform point
    of its life: what it has produced so far arrives as part of its prompt
    (capped at the longest prompt), the rest is its budget."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        n = int(traffic["pool_size"])
        n0 = int(traffic.get("in_flight_at_start", 0))
        shape_rng = np.random.default_rng(int(traffic["shape_seed"]))
        plen = _draw(traffic["prompt_len"], n, shape_rng)
        olen = _draw(traffic["output_len"], n, shape_rng)
        gaps = None
        if traffic["kind"] == "open_loop":
            gaps = _gaps(traffic["arrivals"], float(traffic["rate_per_s"]),
                         n, shape_rng)
        met = shape_rng.choice(n, n0, p=olen / olen.sum())
        done = np.floor(shape_rng.uniform(0, 1, n0) * olen[met]).astype(
            np.int64)
        rng = np.random.default_rng([int(seed), 2])
        order = rng.permutation(n)
        first = rng.permutation(n0)
        self.start_prompt_len = np.minimum(
            plen[met] + done, traffic["prompt_len"]["max"])[first]
        self.start_output_len = (olen[met] - done)[first]
        self.prompt_len = plen[order]
        self.output_len = olen[order]
        self.due = None if gaps is None \
            else np.cumsum(gaps[rng.permutation(n)])
        self.start_prompts = [_ids(p, vocab, rng)
                              for p in self.start_prompt_len]
        self.n, self.vocab, self.seed = n, vocab, int(seed)

    def __len__(self):
        return self.n

    def prompt(self, k: int) -> np.ndarray:
        """Ids of the k-th request submitted (pool entry k mod pool_size)."""
        return _ids(self.prompt_len[k % self.n], self.vocab,
                    np.random.default_rng([self.seed, 2, int(k)]))

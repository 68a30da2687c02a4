"""The program's own spans, over the window the harness timed.

The program keeps its spans in ``paddle_tpu.core.flight_recorder`` (an
in-memory ring on ``time.monotonic_ns``, the clock of the harness's
window): scheduler iterations with their admissions, syncs, dispatches
and polls, each request's queue wait and prefill, set-up, program builds
and train steps, each with an id and its parent's.  The per-layer
readers with ``"source": "program_span"`` go through :func:`load`:

* ``part="window"``: from ``run.t_proc + run.setup_s`` for
  ``run.window_s``; ``part="setup"``: from ``run.t_proc`` to the open;
* spans are picked by where they *start* (a request submitted in the
  window whose first token comes in the grace after it still counts);
* self time = a span's duration less the part its children cover;
* ``None`` when the program has no such recorder (a commit before the
  spans existed), when it is off, or when the ring dropped anything that
  ended inside the interval: a cut window is not read.
"""
from __future__ import annotations

import sys


def _recorder():
    try:
        from paddle_tpu.core import flight_recorder as fr
    except Exception:
        return None
    # the reader of spans with ids and parents, and of what was dropped
    if not (hasattr(fr, "dropped_since") and hasattr(fr, "Span")):
        return None
    return fr if fr.is_enabled() else None


class Spans:
    def __init__(self, spans, t0_ns: int, t1_ns: int):
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.all = spans
        self.by_id = {s.id: s for s in spans}
        self._children = {}
        for s in spans:
            if s.parent is not None:
                self._children.setdefault(s.parent, []).append(s)

    def named(self, name: str, **where):
        """Spans called ``name`` that start inside the interval and whose
        fields match ``where``."""
        return [s for s in self.all if s.name == name
                and self.t0_ns <= s.start_ns < self.t1_ns
                and all(s.fields.get(k) == v for k, v in where.items())]

    def children(self, span):
        return self._children.get(span.id, [])

    def descendants(self, span):
        out, todo = [], list(self.children(span))
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(self.children(s))
        return out

    def self_ms(self, span) -> float:
        """Duration less the part the children cover (children of one
        span do not overlap: they are opened on one thread)."""
        covered = sum(c.end_ns - c.start_ns for c in self.children(span))
        return max(span.end_ns - span.start_ns - covered, 0) / 1e6


def ms(span) -> float:
    return (span.end_ns - span.start_ns) / 1e6


def load(run, part: str = "window"):
    """:class:`Spans` of one part of ``run``, or None (see above); read
    once a run, kept on it."""
    cache = vars(run).setdefault("_program_spans", {})
    if part not in cache:
        cache[part] = _load(run, part)
    return cache[part]


def _load(run, part):
    fr = _recorder()
    if fr is None or run.window_s <= 0:
        return None
    t_open = int((run.t_proc + run.setup_s) * 1e9)
    if part == "setup":
        t0, t1 = int(run.t_proc * 1e9), t_open
    else:
        t0, t1 = t_open, t_open + int(run.window_s * 1e9)
    dropped = fr.dropped_since(t0)
    if dropped:
        print(f"spans: the recorder dropped {dropped} events, some inside "
              f"the {part}: its spans are not read", file=sys.stderr)
        return None
    # to the end of the ring: what starts in the interval may end after it
    return Spans(fr.spans_between(t0, 2 ** 62), t0, t1)


def note(name: str, text: str) -> None:
    print(f"{name}: {text}", file=sys.stderr)


def program_build_s(run):
    """``program_build_s.serve`` / ``.train``: seconds spent building or
    loading programs before the window opened, the sum of set-up's
    ``jit.program`` spans.  By ``source`` on standard error: ``store``
    (a serialized executable loaded), ``persistent_cache`` (lowered,
    then jax's compilation cache answered), ``compile`` (XLA compiled
    it)."""
    sp = load(run, "setup")
    progs = sp and [s for s in sp.named("jit.program")
                    if s.end_ns <= sp.t1_ns]
    if not progs:
        return None
    by = {}
    for s in progs:
        rec = by.setdefault(s.fields.get("source", "?"), [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += ms(s) / 1e3
        rec[2] += s.fields.get("lower_s", 0.0)
    note("program_build_s", ", ".join(
        f"{k}: {n} programs {t:.2f} s (lowering {low:.2f} s)"
        for k, (n, t, low) in sorted(by.items())))
    return sum(ms(s) for s in progs) / 1e3

"""Serving request front-end: Future-style handles + admission types.

A :class:`Request` is both the scheduler's bookkeeping record and the
caller's handle: ``submit()`` returns it immediately, ``result()``
blocks until the request reaches a terminal status (pumping the engine
inline when no background pump thread owns it, so a single-threaded
caller can ``submit(); result()`` without deadlocking).

Terminal statuses and how a request gets there:

    COMPLETED   decoded to eos or its token budget
    CANCELLED   deadline expired (queued or mid-decode), or the drain
                timeout hit during a graceful shutdown
    REJECTED    queue at bound when submitted, or still queued when a
                shutdown drain started

``result()`` returns the generated token ids for COMPLETED and raises
:class:`RequestFailed` otherwise (partial tokens, if any, stay on
``handle.tokens``).
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import os
import threading
import time
from typing import Optional

import numpy as np

from ..core import flight_recorder

__all__ = ["QueueFull", "Request", "RequestFailed", "RequestParams",
           "RequestStatus"]


class RequestStatus(str, enum.Enum):
    QUEUED = "queued"
    #: chunked prefill in flight: the request owns a slot (and its
    #: committed pages) but its prompt is only partially written — the
    #: scheduler never decodes a PENDING_PREFILL slot; the final chunk's
    #: admission flips it to RUNNING
    PENDING_PREFILL = "pending_prefill"
    RUNNING = "running"
    COMPLETED = "completed"
    CANCELLED = "cancelled"
    REJECTED = "rejected"

    @property
    def terminal(self) -> bool:
        return self in (RequestStatus.COMPLETED, RequestStatus.CANCELLED,
                        RequestStatus.REJECTED)


@dataclasses.dataclass(frozen=True)
class RequestParams:
    """Per-request knobs. ``max_new_tokens`` must not exceed the
    engine's compiled budget (the out-buffer width); ``deadline_s`` is
    relative to submit time — a request still queued or still decoding
    past it is cancelled with a timeout status."""
    max_new_tokens: Optional[int] = None
    deadline_s: Optional[float] = None


class QueueFull(RuntimeError):
    """Admission control: the request queue is at its depth bound.

    ``reason`` carries the structured health reason — ``queue_full``
    (blocker not yet known: a submit burst between scheduler steps),
    ``queue_full:no_free_slots`` (admission capacity — another replica
    would help), ``queue_full:no_free_pages`` (KV memory pressure —
    only a replica with pool headroom helps) — and ``request`` the
    already-terminal REJECTED handle, so a router or external LB can
    tell retryable pressure from a terminal drain without parsing the
    message."""

    def __init__(self, msg: str = "", *, reason: str = "queue_full",
                 request: Optional["Request"] = None):
        super().__init__(msg)
        self.reason = reason
        self.request = request


class RequestFailed(RuntimeError):
    """result() on a request that did not complete."""

    def __init__(self, status: RequestStatus, detail: str):
        super().__init__(f"request {status.value}: {detail}")
        self.status = status
        self.detail = detail


_ids = itertools.count()


class Request:
    """One submitted prompt: scheduler record + caller handle."""

    def __init__(self, prompt: np.ndarray, params: RequestParams,
                 budget: int, deadline: Optional[float], engine=None):
        self.id = next(_ids)
        self.prompt = prompt                  # [plen] int32
        self.params = params
        self.budget = int(budget)             # tokens incl. the prefill one
        self.deadline = deadline              # absolute monotonic, or None
        self.status = RequestStatus.QUEUED
        self.detail = ""
        self.tokens: Optional[np.ndarray] = None   # eos-trimmed on success
        # block diffusion only: for each of ``tokens``, the denoise step
        # of its block at which it was unmasked (int8); filled with it
        self.unmask_steps: Optional[np.ndarray] = None
        self.n_emitted = 0                    # tokens so far, incl. eos
        self.submitted_at = time.monotonic()
        # when the request LEFT THE QUEUE for a slot (the end of its
        # serve.queue_wait), not "its admission succeeded": a request
        # whose prefill then raises is CANCELLED with this set and
        # first_token_at None
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self._engine = engine
        self._event = threading.Event()
        # ---- per-request tracing: every request carries a trace id and
        # records serve.queue_wait + serve.prefill into the flight
        # recorder; SAMPLED requests (the engine sets traced=True for
        # 1-in-N) additionally record a decode segment per poll (and
        # their prefill chunks), so a dump or a Perfetto export shows
        # how far each in-flight request got. The off path is one
        # attribute check (gated by test_overhead_gate).
        self.trace_id = f"{os.getpid():x}.{self.id}"
        self.traced = False
        self._t_seg_ns = 0      # rolling decode-segment anchor
        # ---- cost attribution (SLO watchtower): the engine charges
        # prefill wall at admission, this request's share of every poll
        # window it was live in, and page*seconds held in the paged
        # pool; read back via cost() and the /slo top-K table
        self._cost_prefill_s = 0.0
        self._cost_decode_s = 0.0
        self._cost_page_s = 0.0

    def span(self, name: str, start_ns: int, end_ns: int, **fields):
        """Record one sampled trace segment for this request (no-op
        unless the engine sampled it). Spans land in the flight recorder
        ring and, through it, in the Profiler's Perfetto export; the tid
        keys each request onto its own trace row."""
        if not self.traced:
            return
        flight_recorder.record_span(
            f"req{self.id}.{name}", start_ns, end_ns,
            trace_id=self.trace_id, tid=1000 + self.id % 64,
            req=self.id, **fields)

    def stage_span(self, name: str, start_ns: int, end_ns: int,
                   **fields):
        """``serve.queue_wait`` / ``serve.prefill``: recorded for EVERY
        request, under its trace id."""
        flight_recorder.record_span(
            name, start_ns, end_ns, trace_id=self.trace_id,
            tid=1000 + self.id % 64, req=self.id, **fields)

    # ------------------------------------------------------------ handle
    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until terminal. Without a background pump thread the
        calling thread drives the engine itself, so a synchronous
        ``submit(); result()`` makes progress instead of deadlocking."""
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        while not self._event.is_set():
            pumped = self._engine._try_pump() \
                if self._engine is not None else False
            if not pumped:
                self._event.wait(0.005)
            if deadline is not None and time.monotonic() > deadline \
                    and not self._event.is_set():
                raise TimeoutError(
                    f"request {self.id} not finished within {timeout}s "
                    f"(status {self.status.value})")
        if self.status is RequestStatus.COMPLETED:
            return self.tokens
        raise RequestFailed(self.status, self.detail)

    # --------------------------------------------------------- scheduler
    def _finish(self, status: RequestStatus, detail: str = ""):
        """Terminal transition; idempotent (a drain racing a completion
        keeps the first outcome). Records the terminal event — and, for
        sampled requests, the final trace segment — into the flight
        recorder, so a dump taken moments later explains every request
        that just ended."""
        if self._event.is_set():
            return
        self.status = status
        self.detail = detail
        t = flight_recorder.now_ns()
        self.finished_at = t * 1e-9
        if flight_recorder.enabled:
            flight_recorder.record(
                "serve.finish", t_ns=t, req=self.id, status=status.value,
                tokens=self.n_emitted,
                **({"detail": detail} if detail else {}))
            if self.admitted_at is None:
                # never left the queue: its whole life was queue wait
                self.stage_span("serve.queue_wait",
                                int(self.submitted_at * 1e9), t,
                                status=status.value)
            elif self.traced and self._t_seg_ns:
                self.span("decode", self._t_seg_ns, t,
                          tokens=self.n_emitted, status=status.value)
        self._event.set()

    # ----------------------------------------------------------- timings
    @property
    def ttft(self) -> Optional[float]:
        """Submit -> first token (seconds) — includes queue wait."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def per_token_latency(self) -> Optional[float]:
        """Mean decode seconds/token after the first (None until
        terminal or when the request never decoded)."""
        if self.first_token_at is None or self.finished_at is None \
                or self.n_emitted <= 1:
            return None
        return (self.finished_at - self.first_token_at) / \
            (self.n_emitted - 1)

    def cost(self) -> dict:
        """Attributed resource cost so far: prefill wall seconds, this
        request's share of every decode poll window it was live in
        (window wall / live slots — the shares of one window sum to the
        window, so fleet-wide costs reconcile against the goodput
        ledger's compute bucket), and KV page*seconds held in the
        paged pool (0.0 on contiguous caches)."""
        return {
            "prefill_s": self._cost_prefill_s,
            "decode_s": self._cost_decode_s,
            "page_s": self._cost_page_s,
            "total_s": self._cost_prefill_s + self._cost_decode_s,
        }

    def __repr__(self):
        return (f"Request(id={self.id}, status={self.status.value}, "
                f"prompt={self.prompt.size} toks, budget={self.budget})")

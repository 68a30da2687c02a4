"""Generate docs/metrics.md AND docs/events.md from the declared
schemas.

The registry's schema lives in ``core/monitor.py`` twice: the
``DECLARED_METRICS`` frozenset the framework lint enforces (an
undeclared name recorded anywhere in ``paddle_tpu/`` fails CI) and the
``METRIC_DOC`` table carrying each name's kind, labels and description.
The flight recorder's event schema lives the same way in
``core/flight_recorder.py`` (``DECLARED_EVENTS`` enforced by the
lint's ``event-name`` rule, ``EVENT_DOC`` for descriptions). This tool
renders both tables as markdown references, and the tier-1 drift tests
regenerate them on every run — a schema change that forgets the doc
(or a doc edit that drifts from the schema) fails CI.

    python -m tools.metrics_doc            # rewrite both docs
    python -m tools.metrics_doc --check    # exit 1 if either is stale
"""
from __future__ import annotations

import os
import sys

_HEADER = """\
# Metrics reference

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with `python -m tools.metrics_doc`; the schema lives in
     `paddle_tpu/core/monitor.py` (METRIC_DOC / DECLARED_METRICS). -->

Every metric the framework records, as declared in
`core/monitor.DECLARED_METRICS`. All of them flow through the
process-global registry (`core/metrics.py`): scrape them live from the
telemetry server's `/metrics` (Prometheus text; dots become
underscores, label sets render as `{k="v"}`), snapshot them with
`profiler.metrics.snapshot()`, or watch them as counter tracks in the
Perfetto export. Labeled metrics also keep an unlabeled aggregate
under the same name.

| Metric | Kind | Labels | Description |
|---|---|---|---|
"""


def render() -> str:
    from paddle_tpu.core.monitor import DECLARED_METRICS, METRIC_DOC
    missing = DECLARED_METRICS - set(METRIC_DOC)
    extra = set(METRIC_DOC) - DECLARED_METRICS
    if missing or extra:
        raise SystemExit(
            f"METRIC_DOC out of sync with DECLARED_METRICS: "
            f"missing={sorted(missing)} extra={sorted(extra)}")
    rows = []
    for name in sorted(METRIC_DOC):
        kind, labels, desc = METRIC_DOC[name]
        lab = ", ".join(labels) if labels else "—"
        rows.append(f"| `{name}` | {kind} | {lab} | {desc} |")
    return _HEADER + "\n".join(rows) + "\n"


_EVENTS_HEADER = """\
# Flight-recorder events reference

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with `python -m tools.metrics_doc`; the schema lives in
     `paddle_tpu/core/flight_recorder.py` (EVENT_DOC /
     DECLARED_EVENTS). -->

Every structured point event the framework records into the flight
recorder's ring, as declared in `core/flight_recorder.DECLARED_EVENTS`
(enforced by the `event-name` lint rule). Events surface in auto-dumps
(Perfetto JSON + plaintext tail), `/flightrecorder`, and — merged
across ranks by `tools/trace_merge.py` — the fleet post-mortem
timeline.

| Event | Description |
|---|---|
"""

_SPANS_HEADER = """
## Spans

Every span name declared in `core/flight_recorder.DECLARED_SPANS` (the
same lint rule holds literal names to it). A span carries its id and
its parent's, so a reader (`flight_recorder.spans_between`) can compute
self time; each one opened with `flight_recorder.span()` is also a
`jax.profiler.TraceAnnotation`, so it lies on the host plane of any
device trace being taken. The sampled per-request segments
(`req<id>.decode`, `req<id>.prefill_chunk`; 1 request in
`trace_sample`) carry dynamic names and are not listed.

On the DEVICE plane of such a trace the model's regions are named by
`jax.named_scope`: `embed`, a block's mixer under its own name (`attn`
for an attention layer, `short_conv` for a gated short convolution,
`ssm` for a state-space mixer, inside it `ssm_conv`, `ssm_scan` (a
prefill's chunked scan) or `ssm_update` (a decode step's one-step
update; the kernel's trace name too) and `ssm_norm`;
`block_attn` inside `attn` under block diffusion; `paged_kv_write`
where a paged cache's new rows go through `kernels/paged_write.py`, the
kernel's trace name too), `mlp` (inside it
`moe_router` and `moe_experts` of a dropless expert layer, `moe_shared`
of a shared expert beside one), `lm_head`.

| Span | Opened in, covers (fields) |
|---|---|
"""


def render_events() -> str:
    from paddle_tpu.core.flight_recorder import (DECLARED_EVENTS,
                                                 DECLARED_SPANS,
                                                 EVENT_DOC)
    missing = DECLARED_EVENTS - set(EVENT_DOC)
    extra = set(EVENT_DOC) - DECLARED_EVENTS
    if missing or extra:
        raise SystemExit(
            f"EVENT_DOC out of sync with DECLARED_EVENTS: "
            f"missing={sorted(missing)} extra={sorted(extra)}")
    rows = [f"| `{name}` | {EVENT_DOC[name]} |"
            for name in sorted(EVENT_DOC)]
    spans = [f"| `{name}` | {DECLARED_SPANS[name]} |"
             for name in sorted(DECLARED_SPANS)]
    return (_EVENTS_HEADER + "\n".join(rows) + "\n"
            + _SPANS_HEADER + "\n".join(spans) + "\n")


def _docs_dir() -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, "docs")


def doc_path() -> str:
    return os.path.join(_docs_dir(), "metrics.md")


def events_doc_path() -> str:
    return os.path.join(_docs_dir(), "events.md")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    rc = 0
    for path, text in ((doc_path(), render()),
                       (events_doc_path(), render_events())):
        if "--check" in argv:
            try:
                with open(path, "r", encoding="utf-8") as f:
                    current = f.read()
            except OSError:
                current = ""
            if current != text:
                sys.stderr.write(
                    f"{path} is stale; regenerate with "
                    "`python -m tools.metrics_doc`\n")
                rc = 1
            continue
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
        sys.stderr.write(f"wrote {path}\n")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())

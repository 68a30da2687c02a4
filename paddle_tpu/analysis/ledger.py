"""Program regression ledger: the flagship audits frozen as data.

Every capacity-relevant property the auditor computes — structural
fingerprint, donation coverage, planned peak HBM bytes, per-axis
collective payloads, finding counts — is deterministic for a fixed
program, so it can be COMMITTED: ``docs/programs.json`` holds one entry
per flagship program (TrainStep, the fleet step on the default mesh,
the generation prefill/decode pair plus the speculative draft/verify
programs, a Predictor bucket, and the ServingEngine trio in its dense,
paged, and paged-int8 variants). A tier-1 drift gate (the
``docs/metrics.md`` precedent) regenerates the manifest in-process and
compares byte-for-byte — a PR that silently drops a donation, bakes a
constant into a program, or grows its peak HBM fails CI with a JSON
diff that names the program and the field, instead of an on-device OOM
three PRs later.

Deliberate changes refresh the manifest::

    python -m tools.ledger --update     # rewrite docs/programs.json
    python -m tools.ledger --check      # exit 1 on drift (CI form)

The ledger is traced on the CPU backend (tier-1's backend) at the
tier-1 virtual device count (8 — the fleet step's default mesh, and
so its fingerprint, depend on it): kernel selection differs on TPU,
so ``tools/ledger`` pins ``JAX_PLATFORMS`` and ``XLA_FLAGS`` before
jax imports. Audits are trace-only — regeneration allocates no device
buffers and takes seconds.
"""
from __future__ import annotations

import json
import os
from typing import Dict

LEDGER_VERSION = 1

#: env knobs that change the flagship programs (or side-effect their
#: construction): regeneration must be hermetic to them — tools/ledger
#: clears these before importing jax, and the tier-1 drift gate
#: monkeypatches them away
SCRUB_ENV = ("PADDLE_HBM_BUDGET", "PADDLE_KV_CACHE_DTYPE",
             "PADDLE_KV_PAGE_SIZE", "PADDLE_TELEMETRY_PORT",
             "PADDLE_TRACE_SAMPLE")


def ledger_path() -> str:
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "docs", "programs.json")


def entry_for(report) -> Dict:
    """One committed ledger row from one :class:`AuditReport`: only
    deterministic integers/strings, so regeneration on an unchanged
    tree is byte-stable."""
    mem = report.memory
    return {
        "fingerprint": report.fingerprint,
        "donation_coverage": (round(report.donation_coverage, 4)
                              if report.donation_checked else None),
        "peak_bytes": None if mem is None else mem.peak_bytes,
        "args_bytes": None if mem is None else mem.args_bytes,
        "consts_bytes": None if mem is None else mem.consts_bytes,
        "collective_bytes": {k: int(v) for k, v in
                             sorted(report.collectives.items())},
        "findings": {"errors": len(report.errors),
                     "warnings": len(report.warnings)},
    }


def flagship_reports() -> Dict[str, object]:
    """Build and audit every flagship program on the deterministic
    test-tiny configs (trace-only: nothing executes, no buffers).
    Returns ``{ledger_key: AuditReport}``."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer

    reports: Dict[str, object] = {}

    # ---- TrainStep (the PR-7 flagship gate's exact config)
    from paddle_tpu.models.gpt import gpt
    paddle.seed(0)
    model = gpt("test-tiny")
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    from paddle_tpu.jit.api import TrainStep
    step = TrainStep(model, opt, lambda out, lbl: model.loss(out, lbl))
    ids = np.zeros((2, 16), np.int32)  # avals only: values never enter
    reports["train_step"] = step.audit(
        paddle.to_tensor(ids), paddle.to_tensor(ids.astype(np.int64)))

    # ---- DistributedTrainStep on the default (world) mesh
    from paddle_tpu.distributed import fleet, topology
    prev = topology.get_hybrid_communicate_group()
    try:
        paddle.seed(0)
        fleet.init()
        dmodel = gpt("test-tiny")
        dopt = fleet.distributed_optimizer(optimizer.AdamW(
            learning_rate=1e-3, parameters=dmodel.parameters()))
        dstep = fleet.DistributedTrainStep(
            dmodel, dopt, lambda out, lbl: dmodel.loss(out, lbl))
        reports["fleet_step"] = dstep.audit(
            paddle.to_tensor(ids),
            paddle.to_tensor(ids.astype(np.int64)))
    finally:
        topology.set_hybrid_communicate_group(prev)

    # ---- generation prefill/decode + the speculative program pair
    from paddle_tpu.generation.api import GenerationSession
    sess = GenerationSession(model)
    pre, dec, draft, verify = sess.audit(2, 16, 128,
                                         speculative="ngram")
    reports["generation.prefill"] = pre
    reports["generation.decode"] = dec
    reports["generation.spec_draft"] = draft
    reports["generation.spec_verify"] = verify

    # ---- Predictor AOT bucket (the serving-bucket program family)
    from paddle_tpu.inference import Config, create_predictor
    cfg = Config().from_layer(
        model, input_spec=[paddle.to_tensor(ids)])
    cfg.enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                          max_batch=2, eos_token_id=None)
    bucket = create_predictor(cfg).audit_generation()
    reports["predictor.prefill.16"] = bucket[("prefill", 16)]
    reports["predictor.decode.16"] = bucket[("decode", 16)]

    # ---- ServingEngine programs: dense, paged, paged-int8 (the
    # quant variant carries the scale-sidecar geometry through every
    # program, so a misattributed sidecar shows up as byte drift here)
    from paddle_tpu.serving import ServingEngine

    def engine_reports(tag, **serving_kw):
        ecfg = (Config()
                .from_layer(model,
                            input_spec=[paddle.to_tensor(ids)])
                .enable_generation(max_new_tokens=8,
                                   prefill_buckets=(16, 32),
                                   max_batch=2, eos_token_id=None)
                .enable_serving(max_queue=8, prefill_chunk_tokens=16,
                                **serving_kw))
        eng = ServingEngine(ecfg, warmup=False)
        rs = eng.audit()
        reports[f"{tag}.prefill.32"] = rs[("prefill", 32)]
        for prog in ("decode", "admit", "free", "poll_view"):
            reports[f"{tag}.{prog}"] = rs[prog]
        # chunked-prefill programs (enabled on every flagship engine so
        # the ledger pins their geometry): the chunk/final pair always,
        # the span install only where a page table exists
        reports[f"{tag}.prefill_chunk.16"] = rs[("chunk", 16)]
        reports[f"{tag}.prefill_chunk_final.16"] = rs[("chunk_final", 16)]
        if ("install_span",) in rs:
            reports[f"{tag}.install_span"] = rs[("install_span",)]

    engine_reports("serve")
    engine_reports("serve_paged", paged=True, kv_page_size=16)
    engine_reports("serve_quant", paged=True, kv_page_size=16,
                   kv_cache_dtype="int8")
    return reports


def build_ledger() -> Dict:
    return {
        "version": LEDGER_VERSION,
        "backend": "cpu",
        "programs": {name: entry_for(rep)
                     for name, rep in flagship_reports().items()},
    }


def render(ledger: Dict = None) -> str:
    """The exact committed byte content of docs/programs.json."""
    return json.dumps(build_ledger() if ledger is None else ledger,
                      indent=2, sort_keys=True) + "\n"


def check(path: str = None, fresh: Dict = None) -> list:
    """Differences between the committed manifest and a fresh
    regeneration, as human-readable strings (empty = green). The
    tier-1 drift gate asserts this is empty. Pass ``fresh`` to diff
    against an already-built ledger (the gate builds once and checks
    both drift and byte stability from it)."""
    path = path or ledger_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            committed = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path} unreadable ({e}); run "
                "`python -m tools.ledger --update`"]
    if fresh is None:
        fresh = build_ledger()
    diffs = []
    if committed.get("version") != fresh["version"]:
        diffs.append(f"version: {committed.get('version')} != "
                     f"{fresh['version']}")
    old_p = committed.get("programs", {})
    new_p = fresh["programs"]
    for name in sorted(set(old_p) | set(new_p)):
        if name not in old_p:
            diffs.append(f"{name}: NEW program (not in the committed "
                         "ledger)")
            continue
        if name not in new_p:
            diffs.append(f"{name}: committed but no longer built")
            continue
        for field in sorted(set(old_p[name]) | set(new_p[name])):
            a, b = old_p[name].get(field), new_p[name].get(field)
            if a != b:
                diffs.append(f"{name}.{field}: committed {a!r} != "
                             f"regenerated {b!r}")
    return diffs


def update(path: str = None) -> str:
    path = path or ledger_path()
    text = render()
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    return path

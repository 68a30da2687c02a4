"""Detector passes over a traced program's jaxpr.

Each detector is ``fn(ctx) -> List[Finding]`` over an ``AuditContext``
(the closed jaxpr plus flattened input/output avals and the donation
mask). New project-specific detectors register via
``register_detector`` and run in every subsequent ``audit()``.

The built-in passes encode the invariants PRs 2-6 fought for, as
machine-checked rules instead of one bespoke runtime test each:

  donation      inputs whose buffer an output could reuse but that are
                not donated (doubles peak HBM for train state/KV cache)
  host_sync     pure_callback / io_callback (ERROR) and debug_callback
                (WARNING) equations — a hot-path program must never
                round-trip to Python per step
  dtype         fp64 anywhere (ERROR; one stray np scalar flips whole
                subgraphs to f64 under x64), and — opt-in via
                ``bf16_compute=True`` — f32 results computed from bf16
                inputs (weak-type promotion leaks inside a
                declared-bf16 region)
  constants     literal consts baked into the program over a byte
                budget (compile bloat; usually a captured array that
                should have been an argument)
  quant_escape  a quantized (int8/uint8/int4) buffer widened to a
                float dtype OUTSIDE a registered dequant site
                (WARNING): the int8 KV cache and packed int4 weights
                are sanctioned low-bit storage whose ONLY legal exit
                is the fused dequant in the decode kernels /
                precision.materialize — any other wide consumer is
                either missing its scales (silently wrong numerics)
                or re-widening storage the quantization exists to
                keep narrow
  collectives   per-mesh-axis collective payload bytes, statically
                accounted for cross-checking against the runtime
                ``comm.bytes{axis=...}`` counters (PR 2)
  memory        donation-aware buffer liveness (memory.py): peak live
                HBM bytes per program as a MemoryPlan on
                ``report.memory``, and a ``mem.budget`` ERROR when the
                peak exceeds the declared budget
                (``audit(hbm_budget=)`` / ``PADDLE_HBM_BUDGET``)
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .findings import Finding, Severity
from .jaxpr_utils import aval_bytes, source_of, walk_closed, walk_eqns

# primitive name -> severity for host round-trip hazards
_CALLBACK_PRIMS = {
    "pure_callback": Severity.ERROR,
    "io_callback": Severity.ERROR,
    "debug_callback": Severity.WARNING,  # jax.debug.callback / breakpoint
    "debug_print": Severity.WARNING,     # jax.debug.print
}

# collective primitives whose payload we account per mesh axis
_COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "psum_scatter", "reduce_scatter", "all_to_all", "pgather",
})

_F64_DTYPES = (np.dtype("float64"), np.dtype("complex128"))


def _np_dtype(dt) -> Optional[np.dtype]:
    """np.dtype(dt), or None for jax extended dtypes (PRNG keys,
    float8 variants numpy can't interpret)."""
    try:
        return np.dtype(dt)
    except TypeError:
        return None


@dataclasses.dataclass
class AuditContext:
    """Everything a detector pass may inspect. ``in_avals``/``donated``
    align 1:1 with the jaxpr's invars (flattened); ``options`` carries
    audit() keyword knobs (const_budget_bytes, min_donation_bytes,
    bf16_compute, ...)."""
    closed_jaxpr: object
    name: str
    in_avals: List[object]
    donated: List[bool]
    out_avals: List[object]
    options: dict

    def opt(self, key, default=None):
        return self.options.get(key, default)


# ------------------------------------------------------------- donation

def _shape_key(aval) -> Optional[Tuple]:
    shape = getattr(aval, "shape", None)
    dtype = _np_dtype(getattr(aval, "dtype", None))
    if shape is None or dtype is None:
        return None
    return (tuple(shape), dtype.str)


def detect_donation(ctx: AuditContext) -> List[Finding]:
    """Inputs whose shape/dtype matches an output but are not donated:
    XLA must then allocate a second buffer for the output, doubling
    peak memory for exactly the big carried-state arrays (params, opt
    state, KV cache) this framework donates everywhere. Tiny inputs
    (< min_donation_bytes, default 1 KiB — lr scalars, step counters,
    eos flags) are never worth donating and are ignored."""
    min_bytes = int(ctx.opt("min_donation_bytes", 1024))
    out_slots = Counter(k for k in (_shape_key(a) for a in ctx.out_avals)
                        if k is not None)
    findings: List[Finding] = []
    donated_bytes = missed_bytes = unused_bytes = 0

    # donated inputs claim their matching output slot first (that is
    # exactly the pairing XLA's donation matcher performs)
    for aval, don in zip(ctx.in_avals, ctx.donated):
        if not don:
            continue
        key = _shape_key(aval)
        b = aval_bytes(aval)
        if key is not None and out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
            donated_bytes += b
        elif b >= min_bytes:
            unused_bytes += b
            findings.append(Finding(
                "donation.unused", Severity.INFO,
                f"donated input {key and key[0]} {key and key[1]} "
                f"({b} bytes) matches no output; the donation is a "
                "no-op (jax warns at dispatch)", data={"bytes": b}))

    for aval, don in zip(ctx.in_avals, ctx.donated):
        if don:
            continue
        key = _shape_key(aval)
        b = aval_bytes(aval)
        if key is None or b < min_bytes:
            continue
        if out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
            missed_bytes += b
            findings.append(Finding(
                "donation.miss", Severity.WARNING,
                f"input {key[0]} {key[1]} ({b} bytes) matches an "
                "output but is not donated: the update allocates a "
                "second copy instead of writing in place",
                data={"bytes": b, "shape": key[0], "dtype": key[1]}))

    total = donated_bytes + missed_bytes
    ctx.options["_donation"] = {
        "donated_bytes": donated_bytes, "missed_bytes": missed_bytes,
        "unused_bytes": unused_bytes,
        "coverage": (donated_bytes / total) if total else 1.0}
    return findings


# ---------------------------------------------------------- host syncs

def detect_host_callbacks(ctx: AuditContext) -> List[Finding]:
    """pure_callback / io_callback / debug_callback equations anywhere
    in the program (any nesting depth): each one is a host round-trip
    serialized into the device program — in a hot-path program that is
    a per-step sync the async pipeline can never hide."""
    findings = []
    for eqn, _, _ in walk_eqns(ctx.closed_jaxpr):
        sev = _CALLBACK_PRIMS.get(eqn.primitive.name)
        if sev is None:
            continue
        findings.append(Finding(
            "host_sync.callback", sev,
            f"{eqn.primitive.name} inside the compiled program "
            "(host round-trip per step)",
            source=source_of(eqn),
            data={"primitive": eqn.primitive.name}))
    return findings


# --------------------------------------------------------- dtype leaks

def detect_dtype_leaks(ctx: AuditContext) -> List[Finding]:
    findings = []
    seen_f64 = set()

    def _flag_f64(aval, source, what):
        dt = _np_dtype(getattr(aval, "dtype", None))
        if dt is None or dt not in _F64_DTYPES:
            return
        key = (source, str(dt), what)
        if key in seen_f64:
            return
        seen_f64.add(key)
        findings.append(Finding(
            "dtype.fp64", Severity.ERROR,
            f"{np.dtype(dt).name} {what} (fp64 is never intended on "
            "TPU: 10-20x slower and usually a stray numpy default)",
            source=source))

    # index each input/const into its message: with source info absent
    # here, the index is both the dedup key and the only handle the
    # maintainer has on WHICH of N operands is f64
    for i, v in enumerate(ctx.closed_jaxpr.jaxpr.invars):
        shape = tuple(getattr(v.aval, "shape", ()))
        _flag_f64(v.aval, "", f"program input #{i} {shape}")
    for i, v in enumerate(ctx.closed_jaxpr.jaxpr.constvars):
        shape = tuple(getattr(v.aval, "shape", ()))
        _flag_f64(v.aval, "", f"baked constant #{i} {shape}")
    for eqn, _, _ in walk_eqns(ctx.closed_jaxpr):
        src = source_of(eqn)
        for v in eqn.outvars:
            _flag_f64(v.aval, src, f"result of {eqn.primitive.name}")

    if ctx.opt("bf16_compute", False):
        # declared-bf16 region: any f32 value computed FROM bf16 inputs
        # is a promotion leak (a f32/weak-f64 scalar or an implicit
        # upcast re-widens the compute the caller declared narrow);
        # pure-f32 islands (loss accumulators fed by f32) don't match.
        for eqn, _, _ in walk_eqns(ctx.closed_jaxpr):
            in_dts = [_np_dtype(v.aval.dtype) for v in eqn.invars
                      if hasattr(v.aval, "dtype")]
            out_dts = [_np_dtype(v.aval.dtype) for v in eqn.outvars
                       if hasattr(v.aval, "dtype")]
            if any(d is not None and d.name == "bfloat16"
                   for d in in_dts) and \
                    any(d is not None and d.name == "float32"
                        for d in out_dts):
                findings.append(Finding(
                    "dtype.bf16_upcast", Severity.WARNING,
                    f"{eqn.primitive.name} widens bfloat16 input(s) to "
                    "float32 inside a declared-bf16 region (weak-type "
                    "promotion leak: check scalar operand dtypes)",
                    source=source_of(eqn)))
    return findings


# ------------------------------------------------------ baked constants

def detect_baked_constants(ctx: AuditContext) -> List[Finding]:
    """Closure-captured arrays baked into the program as literal
    consts. Small consts are normal (masks, eps); anything over the
    budget bloats every compile, is re-hashed on every jit cache probe,
    and usually should have been an argument (params captured by value
    also silently stop receiving optimizer updates)."""
    budget = int(ctx.opt("const_budget_bytes", 1 << 20))
    findings = []
    total = 0
    for closed in walk_closed(ctx.closed_jaxpr):
        consts = getattr(closed, "consts", None) or []
        constvars = getattr(closed.jaxpr, "constvars", [])
        for var, val in zip(constvars, consts):
            b = aval_bytes(var.aval) or int(getattr(val, "nbytes", 0))
            total += b
            if b >= budget:
                key = _shape_key(var.aval)
                findings.append(Finding(
                    "const.baked", Severity.ERROR,
                    f"constant {key and key[0]} {key and key[1]} "
                    f"({b} bytes) baked into the program (budget "
                    f"{budget}); pass it as an argument instead",
                    data={"bytes": b}))
    ctx.options["_const_bytes"] = total
    return findings


# ------------------------------------------------------- quant escapes

# integer storage dtypes the low-bit serving paths use; a float value
# computed FROM one of these is a dequantization
_QUANT_DTYPE_NAMES = frozenset({"int8", "uint8", "int4", "uint4"})

#: source substrings where int8/int4 -> float widening is sanctioned:
#: the decode kernels' fused dequant, the serving-precision
#: materialize, and the quantization package's own dequant helpers.
#: Project code adding a dequant site registers it here.
QUANT_DEQUANT_SITES = {
    "kernels/flash_attention.py", "inference/precision.py",
    "quantization/int8_compute.py", "quantization/fake_quant.py",
    "quantization/ptq.py", "generation/kv_cache.py",
    "generation/paged_cache.py",
}


def register_dequant_site(source_substring: str) -> str:
    """Sanction a source location (file-path substring matched against
    each finding's ``file.py:line`` provenance) as a legal
    quantized-to-wide dequant site; ``dtype.quant_escape`` stops
    firing there. Returns the substring for decorator-ish use."""
    QUANT_DEQUANT_SITES.add(str(source_substring))
    return source_substring


def detect_quant_escape(ctx: AuditContext) -> List[Finding]:
    """A quantized buffer (int8/int4 — the KV cache pools, packed
    weights) consumed into a FLOAT result outside a registered dequant
    site. Integer-world ops (scatter writes into the cache, page
    gathers, nibble shifts, int8 MXU dots accumulating int32) pass
    freely; the moment a quantized value widens to float anywhere but
    the sanctioned sites, the scales are almost certainly missing —
    WARNING, so the audit gate stays meaningful without blocking
    legitimate new dequant sites (register them)."""
    findings = []
    for eqn, _, _ in walk_eqns(ctx.closed_jaxpr):
        quant_in = False
        for v in eqn.invars:
            dt = _np_dtype(getattr(v.aval, "dtype", None))
            if dt is not None and dt.name in _QUANT_DTYPE_NAMES:
                quant_in = True
                break
        if not quant_in:
            continue
        # name-based float check: np.issubdtype(bfloat16, floating) is
        # FALSE (ml_dtypes extension type), and bf16 is exactly the
        # wide dtype TPU serving dequantizes into — the same gap
        # detect_dtype_leaks works around by name
        out_float = False
        for v in eqn.outvars:
            dt = _np_dtype(getattr(v.aval, "dtype", None))
            if dt is not None and (np.issubdtype(dt, np.floating)
                                   or dt.name == "bfloat16"):
                out_float = True
                break
        if not out_float:
            continue
        src = source_of(eqn) or ""
        if any(site in src for site in QUANT_DEQUANT_SITES):
            continue
        findings.append(Finding(
            "dtype.quant_escape", Severity.WARNING,
            f"{eqn.primitive.name} widens a quantized (int8/int4) "
            "buffer to float outside a registered dequant site — the "
            "dequant scales are probably missing; route through the "
            "fused kernel/materialize paths or "
            "analysis.register_dequant_site() the new site",
            source=src or None,
            data={"primitive": eqn.primitive.name}))
    return findings


# ------------------------------------------------- collective accounting

def detect_collectives(ctx: AuditContext) -> List[Finding]:
    """Static per-mesh-axis collective payload accounting: for every
    collective equation, payload = per-shard operand bytes x axis size
    (= the global tensor bytes the runtime ``comm.bytes{axis=...}``
    counters record). The per-axis totals land on
    ``report.collectives`` for budget assertions and for cross-checking
    a measured run (``cross_check_collectives``)."""
    per_axis: Dict[str, int] = {}
    findings = []
    for eqn, axis_sizes, _ in walk_eqns(ctx.closed_jaxpr):
        if eqn.primitive.name not in _COLLECTIVE_PRIMS:
            continue
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if not isinstance(axes, (list, tuple)):
            axes = (axes,)
        shard_bytes = sum(aval_bytes(v.aval) for v in eqn.invars)
        for ax in axes:
            ax = str(ax)
            size = int(axis_sizes.get(ax, 1))
            nbytes = shard_bytes * size
            per_axis[ax] = per_axis.get(ax, 0) + nbytes
            findings.append(Finding(
                "collective.bytes", Severity.INFO,
                f"{eqn.primitive.name} over axis {ax!r}: {nbytes} "
                f"bytes/step ({shard_bytes} per shard x {size})",
                source=source_of(eqn),
                data={"axis": ax, "op": eqn.primitive.name,
                      "bytes": nbytes}))
    ctx.options["_collectives"] = per_axis
    return findings


# -------------------------------------------------------------- registry

# the buffer-liveness pass lives in its own module (memory.py) — it is
# a planner with its own result type (MemoryPlan), not just findings
from .memory import detect_memory  # noqa: E402

DetectorFn = Callable[[AuditContext], List[Finding]]

DETECTORS: Dict[str, DetectorFn] = {
    "donation": detect_donation,
    "host_sync": detect_host_callbacks,
    "dtype": detect_dtype_leaks,
    "constants": detect_baked_constants,
    "quant_escape": detect_quant_escape,
    "collectives": detect_collectives,
    "memory": detect_memory,
}


def register_detector(name: str, fn: DetectorFn):
    """Add a project-specific pass; it runs in every later audit()
    (names must be new — shadowing a built-in is almost certainly an
    accident)."""
    if name in DETECTORS:
        raise ValueError(f"detector {name!r} already registered")
    DETECTORS[name] = fn
    return fn

"""Static HBM planning: donation-aware buffer liveness over jaxprs.

The reference devotes an entire layer to memory (``AllocatorFacade``,
``memory::Alloc`` — PAPER.md §1 layer 1) and ships memory-optimize
passes in its inference stack; the jax-native equivalent is to answer
*will this program fit?* before a single buffer exists. A traced
program is a straight-line tape of equations over explicitly-shaped
buffers, so peak HBM is a linear scan:

  - **args** are resident from dispatch; a DONATED arg's buffer is
    credited back at its last use (XLA aliases it onto a
    shape/dtype-matching output — the same pairing the donation
    detector models), an undonated arg stays resident to the end.
  - **consts** (top-level and every nested ``ClosedJaxpr``'s) are baked
    into the executable and resident for the whole program.
  - **temporaries** appear at their defining equation and die at their
    last use; at each equation the operands and results coexist (a
    matmul holds A, B and C), so the candidate peak is taken AFTER
    allocation and BEFORE frees — except for the donation pairing
    above, which models XLA's in-place aliasing.
  - **outputs** survive to the end.
  - call-like sub-jaxprs (``pjit``/``remat``/custom-derivative bodies)
    are INLINED with their boundary variables aliased, so a temporary
    three ``pjit`` levels down still lands in the right live set;
    control flow (``scan``/``while``/``cond``) stays opaque but
    contributes its body's isolated internal peak as a transient at
    that equation.

The result is a :class:`MemoryPlan` — peak bytes, the top-K live
buffers at the peak with source provenance, and a per-phase breakdown —
and, when a budget is declared (``audit(..., hbm_budget=)`` or
``PADDLE_HBM_BUDGET``), a ``mem.budget`` ERROR finding that fails the
tier-1 audit gates the way every other detector does. The scan is an
*estimate*: XLA's buffer assignment also reuses dead temporaries it is
free to alias, so the plan upper-bounds the resident set; the
predicted-vs-measured test and ``cross_check_memory`` keep the estimate
honest against ``device.max_memory_allocated()``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from .findings import Finding, Severity
from .jaxpr_utils import _sub_jaxprs, aval_bytes, source_of, walk_closed

from jax.extend.core import Literal

#: call-like primitives whose single body jaxpr executes exactly once
#: with the equation's own operands/results as its boundary — safe to
#: inline for liveness (control flow is NOT in this set: a scan body's
#: buffers are transient per iteration, handled as an isolated extra)
_INLINE_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
})

_SUFFIXES = {
    "k": 1 << 10, "kb": 1 << 10, "kib": 1 << 10,
    "m": 1 << 20, "mb": 1 << 20, "mib": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "gib": 1 << 30,
    "t": 1 << 40, "tb": 1 << 40, "tib": 1 << 40,
}


def parse_bytes(value) -> int:
    """``16GiB`` / ``16G`` / ``1.5e9`` / ``123456`` -> bytes (binary
    units throughout — HBM capacities are quoted in GiB). Raises
    ValueError on garbage; 0 and negatives are rejected (a budget of
    nothing is a typo, not a constraint)."""
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        try:
            n = int(value)
        except (ValueError, OverflowError):   # inf / nan literals
            raise ValueError(
                f"unparseable byte size {value!r}") from None
    else:
        text = str(value).strip().lower().replace("_", "")
        mult, num = 1, text
        for suf in sorted(_SUFFIXES, key=len, reverse=True):
            if text.endswith(suf):
                mult, num = _SUFFIXES[suf], text[:-len(suf)].strip()
                break
        try:
            n = int(float(num) * mult)
        except (ValueError, OverflowError):
            # OverflowError: int(float('inf')) and friends — must fold
            # into ValueError or the swallow paths built on it miss it
            raise ValueError(
                f"unparseable byte size {value!r} (want e.g. 16GiB, "
                "512M, or a plain byte count)") from None
    if n <= 0:
        raise ValueError(f"byte size must be positive, got {value!r}")
    return n


def resolve_hbm_budget(explicit=None) -> Optional[int]:
    """The HBM budget in force: an explicit value wins, else
    ``PADDLE_HBM_BUDGET``, else None (no gate). Raises ValueError on a
    garbage explicit value; a garbage ENV value also raises — a budget
    that silently evaporates is worse than no budget."""
    if explicit is not None:
        return parse_bytes(explicit)
    env = os.environ.get("PADDLE_HBM_BUDGET", "").strip()
    if not env or env.lower() in ("0", "off", "none", ""):
        return None
    return parse_bytes(env)


# --------------------------------------------------------------- records

@dataclasses.dataclass
class _Buf:
    """One buffer the scan tracks: an arg leaf, a const, or a value
    produced by an equation."""
    nbytes: int
    kind: str                 # arg | const | temp | out
    label: str
    shape: Tuple
    dtype: str
    source: str = ""
    donated: bool = False


@dataclasses.dataclass
class _Event:
    """One linearized equation: canonical vars it reads/defines plus
    the transient internal peak of any opaque control-flow body."""
    ins: List
    outs: List
    source: str
    prim: str
    extra: int = 0


class MemoryPlan:
    """The planner's answer for one traced program.

    Attributes:
      peak_bytes:   estimated peak live HBM bytes
      peak_source:  ``file.py:line (fn)`` of the equation at the peak
                    ("entry" when the resident args/consts dominate)
      phases:       bytes by phase AT the peak — ``args`` / ``consts`` /
                    ``temps`` / ``outputs`` / ``transient`` (opaque
                    control-flow bodies)
      top:          the top-K live buffers at the peak, largest first:
                    dicts of bytes/kind/shape/dtype/label/source
      args_bytes / consts_bytes / out_bytes: program totals
      arg_bytes:    per-POSITIONAL-audit-arg byte totals (leaf sums in
                    audit() argument order; None when the flattening
                    did not line up)
      donated_bytes: bytes of args credited back by donation
      budget:       the budget the plan was checked against (or None)
    """

    def __init__(self, peak_bytes: int, peak_source: str,
                 phases: Dict[str, int], top: List[dict],
                 args_bytes: int, consts_bytes: int, out_bytes: int,
                 donated_bytes: int, n_eqns: int,
                 arg_bytes: Optional[List[int]] = None):
        self.peak_bytes = int(peak_bytes)
        self.peak_source = peak_source
        self.phases = dict(phases)
        self.top = list(top)
        self.args_bytes = int(args_bytes)
        self.consts_bytes = int(consts_bytes)
        self.out_bytes = int(out_bytes)
        self.donated_bytes = int(donated_bytes)
        self.n_eqns = int(n_eqns)
        self.arg_bytes = arg_bytes
        self.budget: Optional[int] = None

    @property
    def headroom_bytes(self) -> Optional[int]:
        """budget - peak (negative = over budget); None w/o a budget."""
        if self.budget is None:
            return None
        return int(self.budget) - self.peak_bytes

    def summary(self) -> str:
        mib = self.peak_bytes / (1 << 20)
        lines = [f"memory plan: peak {self.peak_bytes} bytes "
                 f"({mib:.1f} MiB) at {self.peak_source or 'entry'}"]
        lines.append("  phases at peak: " + ", ".join(
            f"{k}={v}" for k, v in sorted(self.phases.items())))
        if self.budget is not None:
            lines.append(f"  budget {self.budget} bytes -> headroom "
                         f"{self.headroom_bytes}")
        for t in self.top:
            src = f" [{t['source']}]" if t.get("source") else ""
            lines.append(f"  {t['nbytes']:>12}  {t['kind']:<5} "
                         f"{t['label']}{src}")
        return "\n".join(lines)

    def __repr__(self):
        return (f"MemoryPlan(peak_bytes={self.peak_bytes}, "
                f"n_eqns={self.n_eqns})")


# ---------------------------------------------------------- linearization

def _canon(alias: dict, v):
    while v in alias:
        v = alias[v]
    return v


class _ScopedVar:
    """A per-invocation copy of an inlined sub-jaxpr's Var. JAX caches
    traced ClosedJaxprs, so two call equations of the same jitted
    subfunction share the very same Var OBJECTS — without scoping,
    both invocations' buffers would collapse onto one record and the
    scan would under-count (an optimistic plan is the one failure mode
    a budget gate cannot have)."""
    __slots__ = ("aval",)

    def __init__(self, aval):
        self.aval = aval


def _scoped(scope, v):
    """Translate a raw jaxpr var into the current inlining scope
    (identity at top level)."""
    if scope is None:
        return v
    s = scope.get(v)
    if s is None:
        s = scope[v] = _ScopedVar(v.aval)
    return s


def _buf_of(v, kind: str, label: str, source: str = "",
            donated: bool = False) -> _Buf:
    aval = v.aval
    return _Buf(aval_bytes(aval), kind, label,
                tuple(getattr(aval, "shape", ())),
                str(getattr(aval, "dtype", "")), source, donated)


def _inline_target(eqn):
    """(open_jaxpr, closed_or_None) when the equation is a call whose
    single body runs once with 1:1 boundary vars; None otherwise."""
    if eqn.primitive.name not in _INLINE_PRIMS:
        return None
    for sub, closed in _sub_jaxprs(eqn):
        if len(sub.invars) == len(eqn.invars) and \
                len(sub.outvars) == len(eqn.outvars):
            return sub, closed
    return None


def _linearize(jaxpr, alias: dict, events: List[_Event],
               var_info: Dict[object, _Buf], scope: Optional[dict] = None):
    """Flatten ``jaxpr`` (inlining call-like bodies, aliasing their
    boundary vars onto the caller's) into ``events``; every var that
    can hold bytes gets a ``var_info`` record. Consts are registered
    zero-cost here — the resident const total is accounted ONCE by
    ``walk_closed`` so nothing is double counted across inlining.
    ``scope`` renames this invocation's vars (see :class:`_ScopedVar`):
    each INLINED call site gets a fresh scope, so repeated calls of
    one cached sub-jaxpr keep distinct buffers."""
    for cv in getattr(jaxpr, "constvars", []):
        sv = _scoped(scope, cv)
        if sv not in var_info:
            var_info[sv] = _Buf(0, "const", "const", (), "")
    for eqn in jaxpr.eqns:
        target = _inline_target(eqn)
        if target is not None:
            inner, _closed = target
            inner_scope: dict = {}
            for iv, ov in zip(inner.invars, eqn.invars):
                siv = _scoped(inner_scope, iv)
                if isinstance(ov, Literal):
                    var_info[siv] = _buf_of(iv, "temp", "literal",
                                            source_of(eqn))
                    var_info[siv].nbytes = 0  # inline scalar constant
                else:
                    alias[siv] = _canon(alias, _scoped(scope, ov))
            _linearize(inner, alias, events, var_info, inner_scope)
            for ov, sv in zip(eqn.outvars, inner.outvars):
                sov = _scoped(scope, ov)
                if isinstance(sv, Literal):
                    # constant-valued output: a fresh (tiny) buffer
                    var_info[sov] = _buf_of(
                        ov, "temp", f"{eqn.primitive.name} const out",
                        source_of(eqn))
                    events.append(_Event([], [sov], source_of(eqn),
                                         eqn.primitive.name))
                else:
                    alias[sov] = _canon(alias,
                                        _scoped(inner_scope, sv))
            continue
        extra = 0
        for sub, _closed in _sub_jaxprs(eqn):
            extra = max(extra, _isolated_extra(sub))
        src = source_of(eqn)
        ins, seen = [], set()
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            c = _canon(alias, _scoped(scope, v))
            if c not in seen:
                seen.add(c)
                ins.append(c)
        outs = []
        for v in eqn.outvars:
            sv = _scoped(scope, v)
            var_info[sv] = _buf_of(
                v, "temp",
                f"{eqn.primitive.name} "
                f"{tuple(getattr(v.aval, 'shape', ()))} "
                f"{getattr(v.aval, 'dtype', '')}", src)
            outs.append(sv)
        events.append(_Event(ins, outs, src, eqn.primitive.name, extra))


def _isolated_extra(jaxpr) -> int:
    """Internal peak of an opaque control-flow body: its boundary
    (invars/constvars) is counted by the caller's live set, so only
    buffers PRODUCED inside contribute. Recursion handles nesting."""
    alias: dict = {}
    events: List[_Event] = []
    var_info: Dict[object, _Buf] = {}
    for v in list(jaxpr.invars) + list(getattr(jaxpr, "constvars", [])):
        var_info[v] = _Buf(0, "arg", "boundary", (), "")
    _linearize(jaxpr, alias, events, var_info)
    outset = {_canon(alias, v) for v in jaxpr.outvars
              if not isinstance(v, Literal)}
    peak, _idx, _ = _scan_peak(events, var_info, outset,
                               base_bytes=0, live0=())
    return peak


# -------------------------------------------------------------- the scan

def _scan_peak(events: List[_Event], var_info: Dict[object, _Buf],
               outset: set, base_bytes: int, live0,
               stop_at: Optional[int] = None):
    """Linear liveness scan. Returns ``(peak, peak_index, live)`` where
    ``peak_index`` is the event index of the peak (-1 = entry) and
    ``live`` is the live var set at ``stop_at`` (used by the second
    pass to reconstruct the peak's live set)."""
    last_use: Dict[object, int] = {}
    for i, ev in enumerate(events):
        for v in ev.ins:
            last_use[v] = i

    live = set(live0)
    cur = base_bytes + sum(var_info[v].nbytes for v in live)
    peak, peak_idx = cur, -1
    for i, ev in enumerate(events):
        dying_donated = [
            v for v in ev.ins
            if (info := var_info.get(v)) is not None
            and info.kind == "arg" and info.donated
            and last_use.get(v) == i and v not in outset and v in live]
        for v in ev.outs:
            if v in live:       # aliased passthrough: no new buffer
                continue
            info = var_info[v]
            # donation credit: XLA aliases a donated dying operand onto
            # a shape/dtype-matching result — in-place, no double count
            for d in dying_donated:
                dinfo = var_info[d]
                if (dinfo.shape, dinfo.dtype) == (info.shape,
                                                  info.dtype):
                    dying_donated.remove(d)
                    live.discard(d)
                    cur -= dinfo.nbytes
                    break
            live.add(v)
            cur += info.nbytes
        if cur + ev.extra > peak:
            peak, peak_idx = cur + ev.extra, i
        if stop_at is not None and i == stop_at:
            return peak, peak_idx, live
        for v in list(ev.ins) + list(ev.outs):
            if v not in live or v in outset:
                continue
            if last_use.get(v, -1) <= i:
                info = var_info[v]
                if info.kind == "temp" or (info.kind == "arg"
                                           and info.donated):
                    live.discard(v)
                    cur -= info.nbytes
    return peak, peak_idx, live


def plan_closed(closed_jaxpr, donated: List[bool],
                arg_groups: Optional[List[int]] = None,
                top_k: int = 8) -> MemoryPlan:
    """Build the :class:`MemoryPlan` for one traced ``ClosedJaxpr``.
    ``donated`` aligns with the flattened invars (the auditor's mask);
    ``arg_groups`` — leaves per positional audit argument, in order —
    lets the plan report per-argument byte totals."""
    jaxpr = closed_jaxpr.jaxpr
    alias: dict = {}
    events: List[_Event] = []
    var_info: Dict[object, _Buf] = {}

    invars = list(jaxpr.invars)
    args_bytes = donated_bytes = 0
    for i, v in enumerate(invars):
        don = bool(donated[i]) if i < len(donated) else False
        var_info[v] = _buf_of(
            v, "arg",
            f"arg#{i} {tuple(getattr(v.aval, 'shape', ()))} "
            f"{getattr(v.aval, 'dtype', '')}", donated=don)
        args_bytes += var_info[v].nbytes
        if don:
            donated_bytes += var_info[v].nbytes

    # consts: every ClosedJaxpr in the tree owns buffers baked into the
    # executable — resident for the whole program, counted exactly
    # once. Dedup by object identity: jax caches traced sub-jaxprs, so
    # a helper called at N sites is the SAME ClosedJaxpr N times in
    # the walk but its consts are baked once.
    const_recs: List[_Buf] = []
    seen_closed = set()
    for closed in walk_closed(closed_jaxpr):
        if id(closed) in seen_closed:
            continue
        seen_closed.add(id(closed))
        for var in getattr(closed.jaxpr, "constvars", []):
            b = _buf_of(var, "const",
                        f"const {tuple(getattr(var.aval, 'shape', ()))} "
                        f"{getattr(var.aval, 'dtype', '')}")
            if b.nbytes:
                const_recs.append(b)
    consts_bytes = sum(b.nbytes for b in const_recs)

    _linearize(jaxpr, alias, events, var_info)
    outset = {_canon(alias, v) for v in jaxpr.outvars
              if not isinstance(v, Literal)}
    out_bytes = sum(var_info[v].nbytes for v in outset
                    if v in var_info)

    live0 = tuple(v for v in invars if var_info[v].nbytes)
    peak, peak_idx, _ = _scan_peak(events, var_info, outset,
                                   consts_bytes, live0)
    # second pass reconstructs the live set AT the peak (cheaper than
    # snapshotting every monotone improvement during the first pass)
    if peak_idx >= 0:
        _, _, live_at_peak = _scan_peak(events, var_info, outset,
                                        consts_bytes, live0,
                                        stop_at=peak_idx)
        peak_source = events[peak_idx].source
        transient = events[peak_idx].extra
    else:
        live_at_peak = set(live0)
        peak_source = "entry"
        transient = 0

    phases = {"args": 0, "consts": consts_bytes, "temps": 0,
              "outputs": 0, "transient": transient}
    records: List[_Buf] = list(const_recs)
    for v in live_at_peak:
        info = var_info[v]
        kind = "out" if v in outset else info.kind
        phases["args" if kind == "arg" else
               "outputs" if kind == "out" else "temps"] += info.nbytes
        records.append(dataclasses.replace(info, kind=kind))
    top = [
        {"nbytes": b.nbytes, "kind": b.kind, "shape": list(b.shape),
         "dtype": b.dtype, "label": b.label, "source": b.source}
        for b in sorted(records, key=lambda b: -b.nbytes)[:top_k]]

    arg_bytes = None
    if arg_groups is not None and sum(arg_groups) == len(invars):
        arg_bytes, pos = [], 0
        for n in arg_groups:
            arg_bytes.append(sum(var_info[v].nbytes
                                 for v in invars[pos:pos + n]))
            pos += n
    return MemoryPlan(peak, peak_source, phases, top, args_bytes,
                      consts_bytes, out_bytes, donated_bytes,
                      len(events), arg_bytes)


# ------------------------------------------------------------- detector

def detect_memory(ctx) -> List[Finding]:
    """The ``memory`` audit pass: computes the program's
    :class:`MemoryPlan` (landing on ``report.memory``) and, when a
    budget is in force (``audit(hbm_budget=)`` / ``PADDLE_HBM_BUDGET``),
    emits the ``mem.budget`` ERROR the tier-1 gates fail on."""
    findings: List[Finding] = []
    plan = plan_closed(ctx.closed_jaxpr, ctx.donated,
                       arg_groups=ctx.opt("_arg_groups"),
                       top_k=int(ctx.opt("mem_top_k", 8)))
    try:
        budget = resolve_hbm_budget(ctx.opt("hbm_budget"))
    except ValueError as e:
        budget = None
        findings.append(Finding(
            "mem.budget_invalid", Severity.WARNING,
            f"HBM budget unparseable and therefore NOT enforced: {e}"))
    plan.budget = budget
    ctx.options["_memory"] = plan
    if budget is not None and plan.peak_bytes > budget:
        worst = ", ".join(
            f"{t['nbytes']}B {t['kind']} {t['label']}"
            for t in plan.top[:3])
        findings.append(Finding(
            "mem.budget", Severity.ERROR,
            f"predicted peak {plan.peak_bytes} bytes exceeds the HBM "
            f"budget {budget} (over by {plan.peak_bytes - budget}); "
            f"largest live at peak: {worst}",
            source=plan.peak_source if plan.peak_source != "entry"
            else "",
            data={"peak_bytes": plan.peak_bytes,
                  "budget_bytes": budget,
                  "over_bytes": plan.peak_bytes - budget}))
    return findings


# ------------------------------------------------------- standalone API

def plan_memory(fn, *args, donate=(), static_argnums=(),
                hbm_budget=None, name=None) -> MemoryPlan:
    """Trace ``fn`` on abstract inputs and return its
    :class:`MemoryPlan` directly (the full ``analysis.audit`` with only
    the memory pass selected — nothing executes, no buffer exists)."""
    from .auditor import audit
    report = audit(fn, *args, donate=donate,
                   static_argnums=static_argnums, name=name,
                   checks=("memory",), hbm_budget=hbm_budget)
    return report.memory


def cross_check_memory(report, measured_bytes=None, device=None,
                       rtol: float = 0.25):
    """Cross-check the plan against a MEASURED peak — the
    ``cross_check_collectives`` analog for HBM. Pass the
    ``device.max_memory_allocated()`` delta of exactly one execution of
    the audited program (reset the peak, run once, read it); with
    ``measured_bytes=None`` the current device's peak is read directly.
    Appends a WARNING when the measurement EXCEEDS the plan beyond
    ``rtol`` — the plan is designed as an upper bound of the resident
    set, so an underestimate means the program allocates buffers the
    static scan cannot see (host callbacks materializing arrays,
    backend workspace) and the budget gate is optimistic."""
    plan = getattr(report, "memory", None)
    if plan is None or not getattr(report, "memory_checked", False):
        raise ValueError(
            f"audit[{report.name}] ran without the 'memory' detector "
            "(checks= excluded it); its plan is absent, not zero — "
            "re-audit with the memory pass before cross-checking")
    if measured_bytes is None:
        from .. import device as _device
        measured_bytes = _device.max_memory_allocated(device)
    measured_bytes = int(measured_bytes)
    if measured_bytes > plan.peak_bytes * (1.0 + rtol):
        report.findings.append(Finding(
            "mem.underestimate", Severity.WARNING,
            f"measured peak {measured_bytes} bytes exceeds the "
            f"predicted {plan.peak_bytes} by more than {rtol:.0%}: the "
            "plan is missing allocations (the budget gate is "
            "optimistic for this program)",
            data={"measured": measured_bytes,
                  "predicted": plan.peak_bytes}))
    return report

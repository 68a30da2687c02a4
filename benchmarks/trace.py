"""Reduction of a JAX profiler trace (``.xplane.pb``) to what metrics read.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  What
comes out (:class:`Reduced`) is small and plain, so a recorded reduction
input can sit under ``testdata/`` and the arithmetic be checked on the CPU
(``tests/test_trace.py``):

* ``window_s``: the traced window, from the harness's own ``bench.trace``
  annotation when the trace has it, else the span of the device events;
* ``busy_s``: union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
* ``idle_gaps``: the time between busy intervals, charged to the harness
  span (``generator``, ``submit``, ``engine.step``, ...) that covered most
  of each gap;
* ``device_ops``: self time by operation name (a ``while`` is not charged
  for its body);
* ``programs``: for each compiled program (an "XLA Modules" event), the
  device duration of every execution and the time of the kernel
  operations inside it.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.trace"
UNCOVERED = "(no harness span)"


@dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0
    devices: int = 0
    idle_gaps: list = field(default_factory=list)    # [[span, seconds]]
    device_ops: list = field(default_factory=list)   # [[op, seconds]]
    programs: dict = field(default_factory=dict)
    # programs[name] = {"durations_s": [...], "kernel_s": [...]}

    def program(self, prefix: str):
        """The program whose module name starts with ``prefix`` and that
        ran longest in total (names carry a ``(id)`` suffix)."""
        best = None
        for name, rec in self.programs.items():
            if name.startswith(prefix) and (
                    best is None or sum(rec["durations_s"])
                    > sum(best["durations_s"])):
                best = rec
        return best


_OPCODE = re.compile(r"(?<![\w.%])([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target=\\?"([\w.\-]+)')
_KIND = re.compile(r"kind=(k\w+)")


def short_name(text: str) -> str:
    """The trace prints a TPU op as its whole HLO instruction; keep the
    result name, the opcode, the custom-call target or fusion kind, and
    the first output shape: ``%fusion.7 fusion kLoop f32[16,1023]``."""
    if " = " not in text:
        return text[:120]
    lhs, rhs = text.split(" = ", 1)
    op = _OPCODE.search(rhs)
    extra = _TARGET.search(rhs) or _KIND.search(rhs)
    shape = rhs.split("{", 1)[0].lstrip("(").strip()
    return " ".join(x for x in (lhs, op.group(1) if op else "",
                                extra.group(1) if extra else "",
                                shape[:48]) if x)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, span_names=()) -> dict:
    """The planes of one trace as plain lists: ``{"devices": [{"modules":
    [[name, start_ns, dur_ns]], "ops": [...]}], "spans": [...]}``.  This is
    also the format of ``testdata/*.json.gz``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = set(span_names) | {WINDOW_SPAN}
    devices, spans = [], []

    def is_tpu(plane):
        return plane.name.startswith("/device:") and "TPU" in plane.name

    has_tpu = any(is_tpu(p) for p in data.planes)
    for plane in data.planes:
        if is_tpu(plane):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [[short_name(e.name), e.start_ns,
                                   e.duration_ns] for e in line.events]
            if dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            cpu_ops = []
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif line.name.startswith("tf_XLAPjRtCpuClient") \
                            and not e.name.startswith(
                                ("ThreadpoolListener", "end: ")):
                        cpu_ops.append((e, line.name))
            if cpu_ops and not has_tpu:
                # CPU rehearsal: the host runs the programs; its client
                # threads stand in for the device so that the same code
                # path is exercised.  Never reported as a device metric.
                dev = {"name": "/host:CPU (rehearsal)", "modules": [],
                       "ops": []}
                for e, _ in cpu_ops:
                    st = dict(e.stats)
                    if "hlo_module" not in st:
                        continue
                    dev["ops"].append([e.name, e.start_ns, e.duration_ns])
                    dev["modules"].append(
                        [str(st["hlo_module"]), e.start_ns, e.duration_ns])
                if dev["ops"]:
                    devices.append(dev)
    return {"devices": devices, "spans": spans}


def save_planes(planes: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load_planes(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ops):
    """{op name: ns of self time}: an op that contains others (a loop,
    a conditional) is charged only what its children leave."""
    total = {}
    stack = []   # [end, name, child_ns, dur]
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, dur = stack.pop()
            total[name] = total.get(name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][2] += dur
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        stack.append([start + dur, name, 0.0, dur])
    close(float("inf"))
    return total


def reduce_planes(planes: dict, kernel_op: str = r"tpu_custom_call") -> Reduced:
    out = Reduced()
    spans = planes["spans"]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    devices = planes["devices"]
    if not devices:
        return out
    if win:
        w0 = min(s[1] for s in win)
        w1 = max(s[1] + s[2] for s in win)
    else:
        w0 = min(o[1] for d in devices for o in d["ops"])
        w1 = max(o[1] + o[2] for d in devices for o in d["ops"])
    out.window_s = (w1 - w0) / 1e9
    out.devices = len(devices)
    kern = re.compile(kernel_op)
    busy_total, gaps, selfs = 0.0, {}, {}
    host = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                  key=lambda s: s[1])
    host_starts = [s[1] for s in host]
    for dev in devices:
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in dev["ops"]
                   if s + d > w0 and s < w1]
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        # idle gaps, each charged to the harness span covering most of it
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            cover = {}
            i = bisect.bisect_right(host_starts, g1)
            for name, s, d in host[max(0, i - 64):i]:
                ov = min(s + d, g1) - max(s, g0)
                if ov > 0:
                    cover[name] = cover.get(name, 0.0) + ov
            name = max(cover, key=cover.get) if cover else UNCOVERED
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0)
        in_win = [o for o in dev["ops"] if o[1] + o[2] > w0 and o[1] < w1]
        for name, ns in _self_times(in_win).items():
            selfs[name] = selfs.get(name, 0.0) + ns
        # programs: every execution's duration and its kernel time
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mstarts = [m[1] for m in mods]
        ksum = [0.0] * len(mods)
        for name, s, d in dev["ops"]:
            if not kern.search(name):
                continue
            i = bisect.bisect_right(mstarts, s) - 1
            if i >= 0 and s < mods[i][1] + mods[i][2]:
                ksum[i] += d
        for (name, s, d), k in zip(mods, ksum):
            if s + d <= w0 or s >= w1:
                continue
            rec = out.programs.setdefault(
                name, {"durations_s": [], "kernel_s": []})
            rec["durations_s"].append(d / 1e9)
            rec["kernel_s"].append(k / 1e9)
    n = len(devices)
    out.busy_s = busy_total / n / 1e9
    out.idle_gaps = [[k, v / n / 1e9] for k, v in
                     sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]
    out.device_ops = [[k, v / n / 1e9] for k, v in
                      sorted(selfs.items(), key=lambda kv: -kv[1])[:10]]
    return out


def reduce_trace(trace_dir: str, span_names=(), kernel_op=r"tpu_custom_call"):
    planes = read_xplane(find_xplane(trace_dir), span_names)
    return reduce_planes(planes, kernel_op), planes


def dump_structure(path: str, out_path: str, per_line: int = 12) -> None:
    """By hand: what planes, lines and events one trace holds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    with open(out_path, "w") as f:
        for plane in data.planes:
            lines = list(plane.lines)
            f.write(f"PLANE {plane.name!r} lines={len(lines)}\n")
            for line in lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for e in evs[:per_line]:
                    try:
                        st = {k: (v if not isinstance(v, str) else v[:80])
                              for k, v in list(e.stats)[:12]}
                    except Exception as ex:
                        st = {"stats_error": repr(ex)}
                    f.write(f"    {e.name[:100]!r} start={e.start_ns} "
                            f"dur={e.duration_ns} {st}\n")

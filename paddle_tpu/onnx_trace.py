"""Trace-based ONNX export: jaxpr -> ONNX graph.

Reference analog: python/paddle/onnx/export.py:21 — paddle2onnx walks
the traced Program op-by-op. TPU-native: the model's forward is traced
to a jaxpr (the framework's real IR) and each primitive maps to ONNX
nodes, so ANY traceable composition exports — residual adds,
attention matmuls/softmax, reshapes/transposes, convs/pools — not just
Sequential chains (onnx_proto.export_onnx remains the legacy walker).
Weights arrive as jaxpr constants and become initializers.
dot_general maps to Einsum (opset 12) with a generated equation, which
covers every contraction the MXU sees without shape gymnastics.

The artifact is validated end-to-end by the in-repo numpy evaluator
(onnx_eval.run_onnx) against the framework forward —
tests/test_onnx_trace.py does this for ResNet-18 and an ERNIE encoder
block.
"""
from __future__ import annotations

import string
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from .onnx_proto import _node, _tensor, _value_info, encode_model

__all__ = ["trace_to_onnx"]


class _Frame:
    """Per-jaxpr-invocation variable environment. Inner jaxprs of jit/
    custom_vjp calls are SHARED objects (jax caches them), so their
    vars must be bound per call, never globally."""

    def __init__(self):
        self.env: Dict[Any, str] = {}         # var -> onnx name
        self.cenv: Dict[Any, np.ndarray] = {}  # var -> folded constant


class _Graph:
    def __init__(self):
        self.nodes: List[bytes] = []
        self.inits: List[bytes] = []
        self.const_vals: Dict[str, np.ndarray] = {}  # initializer values
        self.counter = 0
        self.min_opset = 13
        self.dyn_batch: Optional[int] = None  # traced batch size when
        # the export declares the leading input dim symbolic
        self.batch_src: Optional[str] = None  # graph input whose dim 0
        # IS the runtime batch (Shape-of feeds dynamic Expand targets)
        self._batch_1d: Optional[str] = None

    def runtime_batch_1d(self):
        """[1]-shaped int64 tensor holding the RUNTIME batch size,
        emitted once: Shape(input)[0:1]."""
        if self._batch_1d is None:
            shp = self.emit("Shape", [self.batch_src])
            self._batch_1d = self.emit("Slice", [
                shp,
                self.init_const(np.asarray([0], np.int64)),
                self.init_const(np.asarray([1], np.int64)),
                self.init_const(np.asarray([0], np.int64)),
                self.init_const(np.asarray([1], np.int64))])
        return self._batch_1d

    def fresh(self, base="t"):
        self.counter += 1
        return f"{base}_{self.counter}"

    def init_const(self, arr, base="c"):
        name = self.fresh(base)
        arr = np.asarray(arr)
        self.inits.append(_tensor(name, arr))
        self.const_vals[name] = arr
        return name

    def shape_const(self, dims):
        return self.init_const(np.asarray(dims, np.int64), "shape")

    def reshape_to(self, x_name, sizes, in_shape):
        """Emit a Reshape, keeping the graph batch-agnostic when the
        target's leading dim is the (symbolic) traced batch: ONNX
        Reshape dim 0 copies the input's runtime dim."""
        sizes = list(sizes)
        if self.dyn_batch and sizes and in_shape \
                and sizes[0] == self.dyn_batch \
                and in_shape[0] == self.dyn_batch:
            sizes[0] = 0
        return self.emit("Reshape", [x_name, self.shape_const(sizes)])

    def emit(self, op, inputs, n_out=1, **attrs):
        outs = [self.fresh(op.lower()) for _ in range(n_out)]
        self.nodes.append(_node(op, inputs, outs, **attrs))
        return outs[0] if n_out == 1 else outs

    def name_of(self, atom, frame: _Frame):
        from jax.extend.core import Literal
        if isinstance(atom, Literal):
            return self.init_const(np.asarray(atom.val), "lit")
        if atom not in frame.env and atom in frame.cenv:
            frame.env[atom] = self.init_const(frame.cenv[atom], "fold")
        return frame.env[atom]

    def const_of(self, atom, frame: _Frame):
        """Known constant value of a jaxpr atom, or None."""
        from jax.extend.core import Literal
        if isinstance(atom, Literal):
            return np.asarray(atom.val)
        if atom in frame.cenv:
            return frame.cenv[atom]
        name = frame.env.get(atom)
        if name is not None and name in self.const_vals:
            return self.const_vals[name]
        return None


def _einsum_eq(dn, lhs_rank, rhs_rank):
    """Build an einsum equation for dot_general dimension numbers."""
    (lc, rc), (lb, rb) = dn
    letters = iter(string.ascii_lowercase)
    lhs = [None] * lhs_rank
    rhs = [None] * rhs_rank
    out = []
    for i, j in zip(lb, rb):
        ch = next(letters)
        lhs[i] = rhs[j] = ch
        out.append(ch)
    for i, j in zip(lc, rc):
        ch = next(letters)
        lhs[i] = rhs[j] = ch
    for i in range(lhs_rank):
        if lhs[i] is None:
            lhs[i] = next(letters)
            out.append(lhs[i])
    for j in range(rhs_rank):
        if rhs[j] is None:
            rhs[j] = next(letters)
            out.append(rhs[j])
    return f"{''.join(lhs)},{''.join(rhs)}->{''.join(out)}"


def _conv_node(g, eqn, in_names):
    p = eqn.params
    dn = p["dimension_numbers"]
    lhs_spec = dn.lhs_spec   # e.g. (0, 3, 1, 2) means position of N,C,H,W
    rhs_spec = dn.rhs_spec
    out_spec = dn.out_spec
    x, w = in_names
    ndim = len(lhs_spec)
    spatial = ndim - 2
    # transpose input to NCHW order if needed
    nchw = (0, 1) + tuple(range(2, ndim))
    if tuple(lhs_spec) != nchw:
        # lhs_spec[i] = where dim i of logical (N,C,spatial...) lives
        perm = list(lhs_spec)
        x = g.emit("Transpose", [x], perm=perm)
    if tuple(rhs_spec) != nchw:
        w = g.emit("Transpose", [w], perm=list(rhs_spec))
    pads = [pp for pp, _ in p["padding"]] + [pp for _, pp in p["padding"]]
    if any(d != 1 for d in p.get("lhs_dilation", (1,) * spatial)):
        raise NotImplementedError("transposed conv export not supported")
    out = g.emit("Conv", [x, w],
                 strides=list(p["window_strides"]),
                 pads=pads,
                 dilations=list(p.get("rhs_dilation",
                                      (1,) * spatial)),
                 group=int(p.get("feature_group_count", 1)))
    if tuple(out_spec) != nchw:
        # out_spec[i] = where logical dim i lives in the actual output;
        # we produced logical NCHW, so scatter it back
        inv = [0] * ndim
        for logical, actual in enumerate(out_spec):
            inv[actual] = logical
        out = g.emit("Transpose", [out], perm=inv)
    return out


def _reduce_window_node(g, eqn, in_names):
    p = eqn.params
    ndim = len(p["window_dimensions"])
    wd = p["window_dimensions"]
    ws = p["window_strides"]
    pad = p["padding"]
    if wd[0] != 1 or wd[1] != 1:
        raise NotImplementedError(
            "reduce_window over batch/channel dims not exportable")
    kind = "MaxPool" if eqn.primitive.name == "reduce_window_max" \
        else "AveragePool"
    attrs = dict(kernel_shape=list(wd[2:]), strides=list(ws[2:]),
                 pads=[pp for pp, _ in pad[2:]] + [pp for _, pp
                                                   in pad[2:]])
    if kind == "AveragePool":
        # sum-window = mean * k only when the divisor is the full
        # window everywhere — pad cells must count (ONNX default
        # count_include_pad=0 divides by the non-pad count at borders)
        attrs["count_include_pad"] = 1
    out = g.emit(kind, [in_names[0]], **attrs)
    if eqn.primitive.name == "reduce_window_sum":
        k = float(np.prod(wd[2:]))
        out = g.emit("Mul", [out, g.init_const(np.float32(k))])
    return out


def _broadcast_node(g, eqn, in_names):
    p = eqn.params
    shape = list(p["shape"])
    bcd = p["broadcast_dimensions"]
    in_aval = eqn.invars[0].aval
    # reshape to align: put size (or 1) at each broadcast position
    mid = [1] * len(shape)
    for src, dst in enumerate(bcd):
        mid[dst] = in_aval.shape[src]
    x = in_names[0]
    if list(in_aval.shape) != mid:
        x = g.reshape_to(x, mid, in_aval.shape)
    if mid != shape:
        if g.dyn_batch and shape and shape[0] == g.dyn_batch:
            # target's leading dim is the batch: build the Expand
            # shape at RUNTIME from Shape(input), so non-broadcasting
            # consumers (Concat, Einsum) see the true batch too
            rest = g.shape_const(shape[1:]) if len(shape) > 1 else None
            parts = [g.runtime_batch_1d()]
            if rest is not None:
                parts.append(rest)
            tgt = parts[0] if len(parts) == 1 else \
                g.emit("Concat", parts, axis=0)
            x = g.emit("Expand", [x, tgt])
        else:
            x = g.emit("Expand", [x, g.shape_const(shape)])
    return x


def _reduce_node(g, op, eqn, in_names):
    axes = list(eqn.params["axes"])
    g.min_opset = max(g.min_opset, 13)
    if op == "ReduceSum":  # axes as input from opset 13
        return g.emit("ReduceSum",
                      [in_names[0], g.init_const(
                          np.asarray(axes, np.int64), "axes")],
                      keepdims=0)
    return g.emit(op, [in_names[0]], axes=axes, keepdims=0)


_ELEMENTWISE = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
    "max": "Max", "min": "Min", "pow": "Pow", "exp": "Exp",
    "log": "Log", "tanh": "Tanh", "neg": "Neg", "abs": "Abs",
    "sign": "Sign", "erf": "Erf", "sqrt": "Sqrt", "floor": "Floor",
    "ceil": "Ceil", "logistic": "Sigmoid",
}

_IDENTITY_PRIMS = {"stop_gradient", "copy", "device_put",
                   "optimization_barrier"}


def _onnx_dtype(dt) -> Optional[int]:
    """ONNX TensorProto.DataType for a jax dtype (fp types collapse to
    FLOAT in this fp32 exporter)."""
    s = str(dt)
    if "float" in s or s == "bfloat16":
        return 1                   # FLOAT
    if s == "int64":
        return 7
    if s == "int32":
        return 6
    if s == "bool":
        return 9
    return None

_SUBJAXPR_PRIMS = {"jit", "pjit", "closed_call", "remat", "checkpoint",
                   "custom_jvp_call", "custom_vjp_call",
                   "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}


def _sub_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            j = eqn.params[key]
            return j
    raise NotImplementedError(
        f"{eqn.primitive.name}: no inner jaxpr found")


def _walk(g: _Graph, jaxpr, in_names: List[str],
          const_bind=None) -> List[str]:
    frame = _Frame()
    for var, name in zip(jaxpr.invars, in_names):
        frame.env[var] = name
    for var, name in (const_bind or []):
        frame.env[var] = name
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name

        # constant folding: scalar/index math over known constants
        # (e.g. the clipped indices jnp.take builds for unbind) is
        # evaluated here instead of emitted as graph nodes
        cvals = [g.const_of(v, frame) for v in eqn.invars]
        foldable = (all(c is not None for c in cvals)
                    and all(int(np.prod(ov.aval.shape or (1,))) <= 4096
                            for ov in eqn.outvars)
                    # a dynamic-batch export must not bake
                    # batch-leading constants (e.g. zeros_like(ids)
                    # token-type ids) into the graph
                    and not (g.dyn_batch and any(
                        ov.aval.shape
                        and ov.aval.shape[0] == g.dyn_batch
                        for ov in eqn.outvars)))
        if foldable:
            try:
                if prim in _SUBJAXPR_PRIMS:
                    from jax.extend.core import jaxpr_as_fun
                    sub = _sub_jaxpr(eqn)
                    vals = jaxpr_as_fun(sub)(*cvals)
                else:
                    vals = eqn.primitive.bind(*cvals, **eqn.params)
                if not isinstance(vals, (list, tuple)):
                    vals = [vals]
                for var, val in zip(eqn.outvars, vals):
                    frame.cenv[var] = np.asarray(val)
                continue
            except Exception:
                pass  # fall through to graph emission

        ins = [g.name_of(v, frame) for v in eqn.invars]

        if prim in _SUBJAXPR_PRIMS:
            sub = _sub_jaxpr(eqn)
            if hasattr(sub, "jaxpr"):   # ClosedJaxpr
                inner, consts = sub.jaxpr, list(sub.consts)
            else:
                inner, consts = sub, []
            cbind = [(var, g.init_const(np.asarray(c), "w"))
                     for var, c in zip(inner.constvars, consts)]
            if len(ins) > len(inner.invars):
                # num_consts-style leading operands already bound
                ins = ins[len(ins) - len(inner.invars):]
            outs = _walk(g, inner, ins, const_bind=cbind)
            for var, nm2 in zip(eqn.outvars, outs):
                frame.env[var] = nm2
            continue

        if prim in _IDENTITY_PRIMS:
            out = g.emit("Identity", [ins[0]])
        elif prim == "convert_element_type":
            src_dt = eqn.invars[0].aval.dtype
            dst_dt = eqn.outvars[0].aval.dtype
            dst = _onnx_dtype(dst_dt)
            if dst is None or _onnx_dtype(src_dt) == dst:
                # same ONNX type (incl. bf16<->f32 in an fp32 export):
                # no-op
                out = g.emit("Identity", [ins[0]])
            else:
                out = g.emit("Cast", [ins[0]], to=dst)
        elif prim in _ELEMENTWISE:
            out = g.emit(_ELEMENTWISE[prim], ins)
        elif prim == "integer_pow":
            out = g.emit("Pow", [ins[0], g.init_const(
                np.float32(eqn.params["y"]))])
        elif prim == "square":
            out = g.emit("Mul", [ins[0], ins[0]])
        elif prim == "cbrt":
            out = g.emit("Pow", [ins[0], g.init_const(
                np.float32(1.0 / 3.0))])
        elif prim == "rsqrt":
            out = g.emit("Sqrt", ins)
            out = g.emit("Reciprocal", [out])
        elif prim == "dot_general":
            eq = _einsum_eq(eqn.params["dimension_numbers"],
                            len(eqn.invars[0].aval.shape),
                            len(eqn.invars[1].aval.shape))
            out = g.emit("Einsum", ins, equation=eq)
        elif prim == "conv_general_dilated":
            out = _conv_node(g, eqn, ins)
        elif prim in ("reduce_window_max", "reduce_window_sum"):
            out = _reduce_window_node(g, eqn, ins)
        elif prim == "reduce_sum":
            out = _reduce_node(g, "ReduceSum", eqn, ins)
        elif prim == "reduce_max":
            out = _reduce_node(g, "ReduceMax", eqn, ins)
        elif prim == "reduce_min":
            out = _reduce_node(g, "ReduceMin", eqn, ins)
        elif prim == "reshape":
            out = g.reshape_to(ins[0], eqn.params["new_sizes"],
                               eqn.invars[0].aval.shape)
        elif prim == "transpose":
            out = g.emit("Transpose", [ins[0]],
                         perm=list(eqn.params["permutation"]))
        elif prim == "broadcast_in_dim":
            out = _broadcast_node(g, eqn, ins)
        elif prim in ("squeeze", "expand_dims"):
            out = g.reshape_to(ins[0], eqn.outvars[0].aval.shape,
                               eqn.invars[0].aval.shape)
        elif prim == "concatenate":
            out = g.emit("Concat", ins,
                         axis=int(eqn.params["dimension"]))
        elif prim == "select_n":
            if len(ins) != 3:
                raise NotImplementedError("select_n with >2 cases")
            # select_n(pred, on_false, on_true); Where(c, X, Y)=X if c
            out = g.emit("Where", [ins[0], ins[2], ins[1]])
        elif prim == "pad":
            lo_hi = eqn.params["padding_config"]
            if any(i != 0 for _, _, i in lo_hi) or \
                    any(l < 0 or h < 0 for l, h, _ in lo_hi):
                raise NotImplementedError(
                    "interior/negative padding not exportable")
            pads = [l for l, _, _ in lo_hi] + [h for _, h, _ in lo_hi]
            out = g.emit("Pad", [ins[0],
                                 g.init_const(np.asarray(pads, np.int64),
                                              "pads"),
                                 ins[1]], mode="constant")
        elif prim == "slice":
            p = eqn.params
            nd = len(p["start_indices"])
            limits = list(p["limit_indices"])
            in_shape = eqn.invars[0].aval.shape
            if g.dyn_batch and limits and in_shape \
                    and p["start_indices"][0] == 0 \
                    and limits[0] == in_shape[0] == g.dyn_batch:
                # full-extent batch slice: ONNX clamps out-of-range
                # ends, so a huge end keeps the graph batch-agnostic
                limits[0] = 2 ** 62
            out = g.emit("Slice", [
                ins[0],
                g.init_const(np.asarray(p["start_indices"], np.int64)),
                g.init_const(np.asarray(limits, np.int64)),
                g.init_const(np.asarray(range(nd), np.int64)),
                g.init_const(np.asarray(p["strides"] or [1] * nd,
                                        np.int64))])
        elif prim == "gather":
            dn = eqn.params["dimension_numbers"]
            idx = g.const_vals.get(ins[1])
            op_shape = tuple(eqn.invars[0].aval.shape)
            idx_shape = tuple(eqn.invars[1].aval.shape)
            ss = tuple(eqn.params["slice_sizes"])
            if idx is not None and np.asarray(idx).size == 1 \
                    and len(dn.start_index_map) == 1:
                # static-index pattern (unbind/x[i]): Slice + Reshape
                d = dn.start_index_map[0]
                i0 = int(np.asarray(idx).ravel()[0])
                out = g.emit("Slice", [
                    ins[0],
                    g.init_const(np.asarray([i0], np.int64)),
                    g.init_const(np.asarray([i0 + 1], np.int64)),
                    g.init_const(np.asarray([d], np.int64)),
                    g.init_const(np.asarray([1], np.int64))])
                slice_shape = list(eqn.invars[0].aval.shape)
                slice_shape[d] = 1
                out = g.reshape_to(out, eqn.outvars[0].aval.shape,
                                   slice_shape)
            else:
                # dynamic axis-gather (jnp.take / embedding lookup):
                # indices [..., 1], one collapsed slice dim d, full
                # slice sizes elsewhere — exactly ONNX Gather(axis=d).
                # NB: jax's out-of-range fill semantics are NOT
                # preserved; the export assumes in-range indices (the
                # same contract paddle2onnx emits).
                d = dn.start_index_map[0] \
                    if len(dn.start_index_map) == 1 else -1
                K = len(idx_shape) - 1
                R = len(op_shape)
                expected_ss = op_shape[:d] + (1,) + op_shape[d + 1:] \
                    if d >= 0 else None
                expected_off = tuple(
                    list(range(0, d)) + list(range(d + K, R - 1 + K))) \
                    if d >= 0 else None
                if (d < 0 or idx_shape[-1:] != (1,)
                        or dn.collapsed_slice_dims != (d,)
                        or ss != expected_ss
                        or tuple(dn.offset_dims) != expected_off):
                    raise NotImplementedError(
                        "gather outside the axis-gather (jnp.take) "
                        "and static-index patterns is not "
                        "ONNX-exportable")
                flat_idx = g.reshape_to(ins[1], idx_shape[:-1],
                                        idx_shape)
                out = g.emit("Gather", [ins[0], flat_idx], axis=d)
        elif prim == "iota":
            aval = eqn.outvars[0].aval
            dim = eqn.params["dimension"]
            arr = np.broadcast_to(
                np.arange(aval.shape[dim]).reshape(
                    [-1 if i == dim else 1
                     for i in range(len(aval.shape))]),
                aval.shape).astype(np.float32 if "float" in
                                   str(aval.dtype) else np.int64)
            out = g.init_const(arr, "iota")
        elif prim in ("eq", "ne", "lt", "le", "gt", "ge"):
            onnx_op = {"eq": "Equal", "lt": "Less", "gt": "Greater",
                       "le": "LessOrEqual", "ge": "GreaterOrEqual",
                       "ne": None}[prim]
            if onnx_op is None:
                out = g.emit("Equal", ins)
                out = g.emit("Not", [out])
            else:
                out = g.emit(onnx_op, ins)
        elif prim == "and":
            out = g.emit("And", ins)
        elif prim == "or":
            out = g.emit("Or", ins)
        elif prim == "not":
            out = g.emit("Not", ins)
        else:
            if all(c is not None for c in cvals) and g.dyn_batch:
                raise NotImplementedError(
                    f"jaxpr primitive {prim!r} has no ONNX mapping, "
                    f"and dynamic_batch=True blocked constant-folding "
                    f"its batch-leading result (folding would bake "
                    f"the traced batch size); export with "
                    f"dynamic_batch=False or rewrite the model to "
                    f"compute this from the input")
            raise NotImplementedError(
                f"jaxpr primitive {prim!r} has no ONNX mapping yet "
                f"(eqn: {eqn})")
        outs = [out] if isinstance(out, str) else out
        for var, nm2 in zip(eqn.outvars, outs):
            frame.env[var] = nm2
    return [g.name_of(v, frame) for v in jaxpr.outvars]


def trace_to_onnx(fn, example_inputs: Sequence, path: str,
                  opset: int = 13, input_names: Optional[List[str]]
                  = None, dynamic_batch: bool = False) -> str:
    """Trace `fn(*example_inputs)` (a pure function or an eval-mode
    Layer) to a jaxpr and serialize it as ONNX at `{path}.onnx`.
    Weights/constants become initializers. Returns the file path.

    dynamic_batch=True declares batch-sized leading input dims as the
    symbolic 'N' (the reference's dynamic-batch export): Reshapes that
    preserve the batch emit ONNX dim 0 (copy-from-input), Expand
    targets with a batch-leading dim are built at runtime from
    Shape(input), full-extent batch Slices get clamped huge ends, and
    constant folding refuses to bake batch-shaped constants. Caveat:
    the traced batch size is identified by VALUE, so trace with a
    batch unlikely to collide with fixed model dims (e.g. not 3 for a
    3-channel NCHW input ... use 5 or 7)."""
    from .core.tensor import Tensor
    from .nn.layer import Layer

    if isinstance(fn, Layer):
        layer = fn
        was_training = layer.training
        layer.eval()

        def pure(*args):
            out = layer(*[Tensor(a) for a in args])
            return jax.tree_util.tree_map(
                lambda t: t._data if isinstance(t, Tensor) else t, out,
                is_leaf=lambda x: isinstance(x, Tensor))
    else:
        layer = None

        def pure(*args):
            out = fn(*[Tensor(a) for a in args])
            return jax.tree_util.tree_map(
                lambda t: t._data if isinstance(t, Tensor) else t, out,
                is_leaf=lambda x: isinstance(x, Tensor))

    raw_inputs = [a._data if isinstance(a, Tensor) else np.asarray(a)
                  for a in example_inputs]
    try:
        closed = jax.make_jaxpr(pure)(*raw_inputs)
    finally:
        if layer is not None and was_training:
            layer.train()

    g = _Graph()
    g.min_opset = max(g.min_opset, opset)
    const_names = [g.init_const(np.asarray(c), "w")
                   for c in closed.consts]
    in_names = input_names or [f"input_{i}" if i else "input"
                               for i in range(len(raw_inputs))]
    if dynamic_batch and raw_inputs and np.asarray(raw_inputs[0]).ndim:
        g.dyn_batch = int(np.asarray(raw_inputs[0]).shape[0])
        g.batch_src = in_names[0]
    out_names = _walk(g, closed.jaxpr, in_names,
                      const_bind=list(zip(closed.jaxpr.constvars,
                                          const_names)))

    def vi(name, arr):
        elem = _onnx_dtype(np.asarray(arr).dtype) or 1
        shape = list(np.asarray(arr).shape)
        # only dims that ARE the traced batch become symbolic; other
        # inputs keep their concrete (baked) shapes honestly
        if g.dyn_batch and shape and shape[0] == g.dyn_batch:
            shape[0] = None          # dim_param "N" in the writer
        return _value_info(name, shape, elem)

    model = encode_model(
        g.nodes, g.inits,
        inputs=[vi(n, a) for n, a in zip(in_names, raw_inputs)],
        outputs=[_value_info(n, None) for n in out_names],
        opset=g.min_opset)
    out_path = path if path.endswith(".onnx") else path + ".onnx"
    with open(out_path, "wb") as f:
        f.write(model)
    return out_path

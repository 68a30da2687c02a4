"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

Reference analog: python/paddle/incubate/distributed/models/moe/
(moe_layer.py MoELayer, gate/gshard_gate.py, gate/switch_gate.py) dispatching
tokens with the hand-written global_scatter/global_gather collective ops
(paddle/fluid/operators/collective/global_scatter_op.*).

TPU-native (GShard formulation): expert FFN weights are STACKED with a
leading expert dim sharded over 'ep'; routing builds dense dispatch/combine
tensors [tokens, E, capacity] and the dispatch/return become einsums whose
resharding (token-sharded → expert-sharded → token-sharded) XLA lowers to
the same all_to_all pair the reference codes by hand — riding ICI, fused
with the expert matmuls, and differentiable with zero extra code.

Gates: 'naive' (top-k softmax, no aux loss), 'switch' (top-1 + load-balance
loss, Fedus et al.), 'gshard' (top-2 + load-balance loss, Lepikhin et al.).
Auxiliary loss is exposed as `layer.l_aux` (a traced value when called
under jit: read it in the SAME trace, e.g. inside the loss closure —
`aux_loss(model)` sums it over all MoE sublayers).

`DroplessMoE` is the expert layer of present-day sparse models (many
narrow gated experts, several a token): no capacity and no dropped token.
Tokens are sorted by expert and each projection is ONE grouped product
over the experts' stacked weights (`kernels/grouped_matmul.py` on a TPU,
`jax.lax.ragged_dot` elsewhere: `grouped_product`), so the cost is the
rows routed, not `[tokens, E, capacity]`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core import monitor as _monitor
from ...core.tensor import Tensor, dispatch as _dispatch
from ...kernels import grouped_matmul as _gmm
from ...nn import initializer as I
from ...nn.layer import Layer
from .. import topology
from .mp_layers import sharded_constraint


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top_k_routing(gates, top_k: int, capacity: int):
    """Greedy top-k routing with per-expert capacity.

    gates: [T, E] softmax probabilities.
    Returns (combine [T, E, C], dispatch_mask [T, E, C], aux_inputs):
    aux_inputs = (me, ce): mean gate prob and mean top-1 assignment per
    expert, the two factors of the GShard/Switch load-balancing loss.
    """
    t, e = gates.shape
    remaining = gates
    counts = jnp.zeros((e,), jnp.float32)   # tokens already placed / expert
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    me = jnp.mean(gates, axis=0)
    ce = None
    for k in range(top_k):
        idx = jnp.argmax(remaining, axis=1)              # [T]
        mask = _one_hot(idx, e)                          # [T, E]
        if k == 0:
            ce = jnp.mean(mask, axis=0)
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(mask, axis=0) - 1.0 + counts) * mask
        kept = mask * (pos_in_expert < capacity)
        counts = counts + jnp.sum(kept, axis=0)
        weight = jnp.sum(gates * kept, axis=1, keepdims=True)  # [T,1]
        pos = jnp.sum(pos_in_expert * kept, axis=1).astype(jnp.int32)
        cap_oh = _one_hot(pos, capacity) * jnp.sum(kept, axis=1,
                                                   keepdims=True)
        combine = combine + weight[..., None] * kept[..., None] * \
            cap_oh[:, None, :]
        remaining = remaining * (1.0 - mask)
    dispatch_mask = (combine > 0.0).astype(gates.dtype)
    return combine.astype(gates.dtype), dispatch_mask, (me, ce)


def load_balance_loss(me, ce):
    """GShard/Switch aux loss: E * sum_e(me_e * ce_e) — minimized when
    routing is uniform (≈ reference's gate/gshard_gate.py loss)."""
    return me.shape[0] * jnp.sum(me * ce)


class MoEMLP(Layer):
    """Expert-parallel FFN bank + gate (the MoELayer analog).

    Holds stacked expert weights [E, ...] sharded over 'ep'; forward
    routes tokens, runs experts, and combines. l_aux is set per call.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str = "gshard", top_k: Optional[int] = None,
                 capacity_factor: float = 1.25,
                 activation=None, name=None):
        super().__init__()
        if gate not in ("naive", "switch", "gshard"):
            raise ValueError(f"unknown gate type {gate!r}")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.gate_type = gate
        self.top_k = top_k if top_k is not None else \
            {"naive": 2, "switch": 1, "gshard": 2}[gate]
        self.capacity_factor = capacity_factor
        # raw (non-Tensor) activation: runs on jax arrays inside the
        # already-dispatched forward
        self.activation = activation or (lambda x: jax.nn.gelu(x))

        self.gate_weight = self.create_parameter(
            (d_model, num_experts),
            default_initializer=I.XavierUniform())
        self.gate_weight.spec = P()
        self.w1 = self.create_parameter(
            (num_experts, d_model, d_hidden),
            default_initializer=I.XavierUniform())
        self.w1.spec = P("ep", None, "mp")
        self.b1 = self.create_parameter((num_experts, d_hidden),
                                        is_bias=True)
        self.b1.spec = P("ep", "mp")
        self.w2 = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierUniform())
        self.w2.spec = P("ep", "mp", None)
        self.b2 = self.create_parameter((num_experts, d_model),
                                        is_bias=True)
        self.b2.spec = P("ep", None)
        self.l_aux = None

    def capacity(self, num_tokens: int) -> int:
        cap = int(self.capacity_factor * self.top_k * num_tokens /
                  self.num_experts)
        return max(cap, self.top_k)

    def forward(self, x):
        # params go THROUGH dispatch so the eager tape records their
        # grads; aux is an op output so it is differentiable too
        y, aux = _dispatch(
            "moe_mlp", self._impl,
            (x, self.gate_weight, self.w1, self.b1, self.w2, self.b2), {})
        self.l_aux = aux
        return y

    def _impl(self, x, gate_w, w1, b1, w2, b2):
        """Pure-jax body (raw arrays in/out)."""
        shape = x.shape
        m = shape[-1]
        xf = x.reshape(-1, m)                              # [T, M]
        t = xf.shape[0]
        c = self.capacity(t)

        logits = xf.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)            # [T, E]
        combine, disp, (me, ce) = top_k_routing(gates, self.top_k, c)
        if self.gate_type in ("switch", "gshard"):
            aux = load_balance_loss(me, ce)
        else:
            aux = jnp.zeros((), jnp.float32)
        if self.gate_type == "gshard":
            # GShard normalizes over the selected top-2; Switch keeps the
            # raw top-1 prob (router grad flows through the output scale)
            denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
            combine = combine / jnp.where(denom == 0.0, 1.0, denom)

        xe = jnp.einsum("tec,tm->ecm", disp.astype(xf.dtype), xf)
        xe = sharded_constraint(xe, P("ep", None, None))
        h = jnp.einsum("ecm,emh->ech", xe, w1) + b1[:, None, :]
        h = sharded_constraint(h, P("ep", None, "mp"))
        h = self.activation(h)
        ye = jnp.einsum("ech,ehm->ecm", h, w2) + b2[:, None, :]
        ye = sharded_constraint(ye, P("ep", None, None))
        y = jnp.einsum("tec,ecm->tm", combine.astype(xf.dtype), ye)
        return y.reshape(shape), aux


ROUTER_KINDS = ("softmax", "sigmoid")


def grouped_product(xs, w_up, w_down, backend=None, mesh=None):
    """The grouped product an expert layer runs on its sorted rows ``xs``
    [M, H], chosen by what the code can see: the repo's kernel
    (``kernels/grouped_matmul.py``) on a TPU where rows and weights are
    bfloat16, both products' shapes fit it and the expert dimension is
    not sharded (no mesh, or an 'ep' axis of one); XLA's ``ragged_dot``
    everywhere else (the CPU, expert parallelism, float32 training,
    widths off the lane tile). ``w_up`` [E, H, F'] is the first product's
    weights (gate and up side by side, or up alone), ``w_down``
    [E, F, H] the second's."""
    backend = jax.default_backend() if backend is None else backend
    mesh = topology.get_mesh() if mesh is None else mesh
    m, h = xs.shape
    bf16 = all(a.dtype == jnp.bfloat16 for a in (xs, w_up, w_down))
    fits = _gmm.supports(m, h, w_up.shape[2]) \
        and _gmm.supports(m, w_down.shape[1], h)
    whole = mesh is None or dict(mesh.shape).get("ep", 1) == 1
    return _gmm.grouped_matmul \
        if backend == "tpu" and bf16 and fits and whole else _gmm.ragged_dot


def dropless_moe(x, router_w, w_gate_up, w_down, top_k: int,
                 norm_topk_prob: bool = True, router: str = "softmax",
                 select_bias=None, scaling: float = 1.0, *,
                 gated: bool = True, held=None, norm_eps: float = 1e-6):
    """Pure-jax body of :class:`DroplessMoE` on raw arrays.

    x [T, H]; router_w [H, E]; w_gate_up [E', H, 2F] (gate then up;
    ``gated=False``: up alone, [E', H, F]); w_down [E', F, H]. ``E'`` is
    ``E``, or under ``held = (first, count)`` the ``count`` experts from
    ``first`` on that this chip holds of the router's ``E``. Returns
    (y [T, H], rows [E'] int32: how many (token, expert) rows each
    expert computed; under ``held`` one more entry LAST: the rows sent to
    experts held elsewhere).

    Router, ``"softmax"``: softmax over ALL experts in float32, the
    ``top_k`` largest, renormalised over the chosen (``norm_topk_prob``).
    ``"sigmoid"``: a sigmoid score per expert; the ``top_k`` largest of
    ``score + select_bias`` ([E], a bias that takes part in the SELECTION
    only) are chosen, their weights are the scores WITHOUT the bias,
    divided by (their sum + ``norm_eps``) under ``norm_topk_prob``, times
    ``scaling``. Experts: the
    T * top_k (token, expert) rows sorted by expert, one grouped product
    (:func:`grouped_product`) for gate+up, SiLU(gate) * up (ungated:
    relu(up)^2), one for down,
    then each token's k rows weighted and summed in float32. Nothing is dropped; an expert no
    token chose is an empty group. Under ``held`` the router, the top-k
    and the normalisation still go over all ``E``; a row sent to an
    expert outside the share sorts past the last held group, is never
    multiplied and adds nothing: ``y`` is this share's PART of the
    layer's result (the parts of all the shares add up to it)."""
    t, h = x.shape
    e, f = w_down.shape[0], w_down.shape[1]
    with jax.named_scope("moe_router"):
        # a float32 product in earnest (the TPU's default would round
        # both operands to bfloat16): the 8th and 9th probabilities lie
        # close, and which of them is chosen changes the output
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if router == "softmax":
            top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
            if norm_topk_prob:
                top = top / jnp.sum(top, axis=-1, keepdims=True)
        else:
            score = jax.nn.sigmoid(logits)
            biased = score if select_bias is None \
                else score + select_bias.astype(jnp.float32)
            _, idx = jax.lax.top_k(biased, top_k)
            top = jnp.take_along_axis(score, idx, axis=-1)
            if norm_topk_prob:
                top = top / (jnp.sum(top, axis=-1, keepdims=True)
                             + norm_eps)
            top = top * scaling
    with jax.named_scope("moe_experts"):
        flat = idx.reshape(-1)                          # [T*k] expert ids
        if held is not None:
            # this share's own numbering; everything else is one group
            # more, past the last held one
            first, count = held
            flat = jnp.where((flat >= first) & (flat < first + count),
                             flat - first, e)
        order = jnp.argsort(flat, stable=True)          # rows by expert
        rows = jnp.zeros((e + (held is not None),), jnp.int32) \
            .at[flat].add(1)
        xs = x[order // top_k]                          # [T*k, H]
        product = grouped_product(xs, w_gate_up, w_down)
        _monitor.record_moe_path(kernel=product is _gmm.grouped_matmul)
        gu = product(xs, w_gate_up, rows[:e])
        if gated:
            z = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
        else:
            z = jnp.square(jax.nn.relu(gu)).astype(x.dtype)
        ys = product(z, w_down, rows[:e])
        # back to (token, k) order, weight, sum the k rows of a token
        ys = ys[jnp.argsort(order)].reshape(t, top_k, h)
        y = jnp.einsum("tkh,tk->th", ys, top)
    return y.astype(x.dtype), rows


class DroplessMoE(Layer):
    """Dropless sparse-expert FFN: ``num_experts`` experts of width
    ``d_expert``, SiLU-gated or (``gated=False``) ungated relu^2,
    ``top_k`` a token, no drops. A shared expert beside them is the
    model's, not this layer's.
    ``router``: ``"softmax"``, or ``"sigmoid"`` with an optional
    per-expert ``select_bias`` parameter, a ``scaling`` factor and the
    normalisation's ``norm_eps`` (:func:`dropless_moe` states them).
    ``held = (first, count)``: this layer HOLDS ``count`` of the
    ``num_experts`` the router ranks, from ``first`` on (the chip's share
    under expert parallelism), and gives their part of the result.

    Holds the experts stacked: ``gate_up`` [E', d_model, 2 * d_expert]
    (gate then up, side by side so one grouped product feeds both;
    ungated: ``up`` [E', d_model, d_expert]) and ``down`` [E', d_expert,
    d_model], sharded over 'ep'. ``pad_to``: the experts' width as it is
    STORED, in whole multiples of it (columns of zeros in ``up``, rows of
    zeros in ``down``; exact for an ungated expert, ``relu(0)^2 = 0``):
    what lets a width that is not whole lane tiles take the grouped
    kernel. After a forward, ``rows`` holds that call's per-expert row
    counts and ``rows_elsewhere`` the rows it sent to experts it does not
    hold (traced values under jit: read them in the SAME trace —
    :func:`routing_stats`)."""

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int, norm_topk_prob: bool = True,
                 std: float = 0.02, down_std: Optional[float] = None,
                 dtype=None, router: str = "softmax",
                 select_bias: bool = False, scaling: float = 1.0,
                 gated: bool = True, held=None, norm_eps: float = 1e-6,
                 pad_to: int = 1):
        # dtype: the stacked experts are nearly all of a sparse model;
        # built in float32 first, a model served in bfloat16 on one chip
        # would not fit beside its own cast
        super().__init__()
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} outside [1, {num_experts}]")
        if router not in ROUTER_KINDS:
            raise ValueError(f"unknown router kind {router!r}: one of "
                             f"{ROUTER_KINDS}")
        if select_bias and router != "sigmoid":
            raise ValueError("a selection bias belongs to the sigmoid "
                             "router: softmax weights ARE what is ranked")
        if held is not None and not (
                0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= num_experts):
            raise ValueError(f"held {held} outside the router's "
                             f"{num_experts} experts")
        if pad_to > 1 and gated:
            raise ValueError("padding splits gate from up: the ungated "
                             "kind alone is stored padded")
        self.num_experts, self.top_k = num_experts, top_k
        self.norm_topk_prob = bool(norm_topk_prob)
        self.router_kind, self.scaling = router, float(scaling)
        self.gated, self.norm_eps = bool(gated), float(norm_eps)
        self.held = None if held is None else (int(held[0]), int(held[1]))
        self.router = self.create_parameter(
            (d_model, num_experts), default_initializer=I.Normal(0.0, std))
        self.router.spec = P()
        # ranks the experts and weighs nothing (the sigmoid kind only)
        self.select_bias = None
        if select_bias:
            self.select_bias = self.create_parameter(
                (num_experts,), default_initializer=I.Constant(0.0))
            self.select_bias.spec = P()
        here = num_experts if held is None else self.held[1]
        stored = -(-d_expert // pad_to) * pad_to
        up = self.create_parameter(
            (here, d_model, (2 if gated else 1) * stored), dtype=dtype,
            default_initializer=I.Normal(0.0, std))
        up.spec = P("ep", None, None)
        self.down = self.create_parameter(
            (here, stored, d_model), dtype=dtype,
            default_initializer=I.Normal(0.0, down_std or std))
        self.down.spec = P("ep", None, None)
        if gated:
            self.gate_up = up
        else:
            self.up = up
        if stored != d_expert:
            keep = (jnp.arange(stored) < d_expert)
            up.set_value(up._data * keep.astype(up._data.dtype))
            self.down.set_value(
                self.down._data * keep[:, None].astype(up._data.dtype))
        self.rows = self.rows_elsewhere = None

    def forward(self, x):
        shape = x.shape
        bias = () if self.select_bias is None else (self.select_bias,)
        y, rows = _dispatch(
            "dropless_moe",
            lambda x_, r, gu, dn, *b: dropless_moe(
                x_.reshape(-1, shape[-1]), r, gu, dn, self.top_k,
                self.norm_topk_prob, self.router_kind, *b,
                scaling=self.scaling, gated=self.gated, held=self.held,
                norm_eps=self.norm_eps),
            (x, self.router, self.gate_up if self.gated else self.up,
             self.down) + bias, {})
        if self.held is None:
            self.rows, self.rows_elsewhere = rows, None
        else:
            self.rows, self.rows_elsewhere = rows[:-1], rows[-1]
        return y.reshape(shape)


def routing_stats(model: Layer):
    """(rows, rows_max, rows_elsewhere) summed over every
    :class:`DroplessMoE` sublayer's last forward: the (token, expert)
    rows computed, the busiest expert's rows and the rows sent to experts
    held elsewhere (0 where every layer holds all its experts), int32
    scalars (traced under jit: call in the same trace as the forward,
    like :func:`aux_loss`). None when the model has no such layer."""
    total = None
    for layer in model.sublayers(include_self=True):
        if isinstance(layer, DroplessMoE) and layer.rows is not None:
            r, away = (jnp.asarray(0, jnp.int32) if v is None
                       else v._data if isinstance(v, Tensor) else v
                       for v in (layer.rows, layer.rows_elsewhere))
            one = (jnp.sum(r), jnp.max(r), away)
            total = one if total is None \
                else tuple(a + b for a, b in zip(total, one))
    return total


def aux_loss(model: Layer):
    """Sum of l_aux over every MoE sublayer (call in the same trace as
    the forward — the reference sums gate losses the same way in its
    MoE grad-clip integration). Tensor arithmetic keeps it on the eager
    grad tape."""
    total = None
    for layer in model.sublayers(include_self=True):
        la = getattr(layer, "l_aux", None)
        if la is not None:
            total = la if total is None else total + la
    if total is None:
        return Tensor(jnp.zeros((), jnp.float32))
    return total if isinstance(total, Tensor) else Tensor(total)

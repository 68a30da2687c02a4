"""Tensor-parallel layers.

Reference analog: python/paddle/distributed/fleet/layers/mpu/mp_layers.py —
VocabParallelEmbedding(:37), ColumnParallelLinear(:173),
RowParallelLinear(:327), ParallelCrossEntropy(:491), with hand-inserted
collectives from mp_ops.py (_c_identity/_mp_allreduce/_c_split).

TPU-native (GSPMD): layers hold FULL logical weights annotated with a
PartitionSpec over the 'mp' mesh axis; XLA's SPMD partitioner slices the
matmuls and inserts the psum/all_gather the reference writes by hand.
`with_sharding_constraint` pins activation layouts at the seams the
reference's _c_identity/_c_concat mark. The layers therefore run
unchanged on 1 device (specs are no-ops) and partition under a mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.tensor import Tensor, dispatch
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from .. import topology


def _constraint(x_raw, spec):
    """Apply a sharding constraint if a global mesh is active and the
    shape divides the mesh axes (small debug batches skip the pin rather
    than erroring — XLA still propagates shardings without it)."""
    mesh = topology.get_mesh()
    if mesh is None:
        return x_raw
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if dim >= x_raw.ndim or x_raw.shape[dim] % n != 0:
            return x_raw
    try:
        return jax.lax.with_sharding_constraint(
            x_raw, NamedSharding(mesh, spec))
    except Exception:
        return x_raw


def sharded_constraint(x, spec):
    if isinstance(x, Tensor):
        return dispatch("sharding_constraint",
                        lambda a: _constraint(a, spec), (x,), {})
    return _constraint(x, spec)


class ColumnParallelLinear(Layer):
    """Weight [in, out] sharded on out (mp); output shards over mp unless
    gather_output (≈ mp_layers.py:173)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.spec = P(None, "mp")  # out-dim sharded
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.spec = P("mp")
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            out = sharded_constraint(out, P(*([None] * out.ndim)))
        else:
            out = sharded_constraint(
                out, P(*([None] * (out.ndim - 1) + ["mp"])))
        return out


class RowParallelLinear(Layer):
    """Weight [in, out] sharded on in (mp); input expected mp-sharded on its
    last dim; output is psum-reduced by GSPMD (≈ mp_layers.py:327)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.spec = P("mp", None)  # in-dim sharded
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.spec = P()
        else:
            self.bias = None

    def forward(self, x):
        x = sharded_constraint(x, P(*([None] * (x.ndim - 1) + ["mp"])))
        out = F.linear(x, self.weight, None)
        out = sharded_constraint(out, P(*([None] * out.ndim)))
        if self.bias is not None:
            out = out + self.bias
        return out


class VocabParallelEmbedding(Layer):
    """Embedding table sharded on the vocab dim (≈ mp_layers.py:37). GSPMD
    turns the gather into a masked local lookup + psum, the same trick the
    reference's c_embedding op implements by hand
    (operators/collective/c_embedding_op.cu)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0))
        self.weight.spec = P("mp", None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return sharded_constraint(out, P(*([None] * out.ndim)))


class ParallelCrossEntropy(Layer):
    """Cross entropy over mp-sharded logits (≈ mp_layers.py:491 /
    c_softmax_with_cross_entropy_op). Under GSPMD the plain fused
    cross-entropy partitions correctly when logits are mp-sharded on the
    class dim; we pin that layout and let XLA insert the two psums."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        input = sharded_constraint(
            input, P(*([None] * (input.ndim - 1) + ["mp"])))
        return F.cross_entropy(input, label, reduction="none",
                               ignore_index=self.ignore_index)

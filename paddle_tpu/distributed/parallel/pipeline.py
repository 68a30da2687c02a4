"""Pipeline parallelism — SPMD collective pipelining over the 'pp' mesh axis.

Reference analog: python/paddle/distributed/fleet/meta_parallel/
parallel_layers/pp_layers.py:56,76,206 (`LayerDesc`, `PipelineLayer` stage
partitioning with shared-weight groups, seg_method segmentation) and
meta_parallel/pipeline_parallel.py:117-198,457 (`PipelineParallel`
1F1B + `PipelineParallelWithInterleave` virtual stages) with P2P handoff
in pp_utils/p2p_communication.py:344.

TPU-native redesign: instead of per-rank processes exchanging activations
over NCCL P2P with a host-driven 1F1B state machine, the whole pipeline is
ONE SPMD program:

  * the homogeneous trunk's blocks are stacked at BLOCK granularity:
    params live in one array with leading dims [S, v, maxB] (stage,
    virtual chunk, blocks-per-unit) sharded `P('pp')` on the stage dim;
  * a `lax.scan` over the schedule's ticks runs the pipeline: at each
    tick every stage applies its current unit (an inner masked scan over
    its blocks), then activations rotate one hop along the ring via
    `lax.ppermute` (the ICI-neighbor analog of P2P send/recv);
  * **interleaved virtual stages** (`interleave=v`, the
    PipelineParallelWithInterleave analog): each device hosts v chunks;
    virtual microbatches flow chunk-major through the ring v times, so
    the bubble drops from (S-1)/(M+S-1) to (S-1)/(vM+S-1);
  * **unbalanced partition** (`seg_sizes`, the seg_method analog): units
    may hold different numbers of blocks; the inner scan masks the
    padding, so a 7-block trunk on 4 stages is [2,2,2,1] instead of an
    error;
  * `shard_map` is *manual only over 'pp'* (`axis_names={'pp'}`) — dp/
    sharding/mp stay in GSPMD auto mode, so tensor-parallel layers and
    batch sharding inside each stage keep working unchanged;
  * backward is just `jax.grad` through the scan — XLA schedules the
    backward pipeline (the 1F1B memory behaviour is recovered with
    `jax.checkpoint` on the block body instead of a hand-written
    schedule).

The embedding / final-norm / lm-head ("pre"/"post" segments) run
replicated across the pp axis: they are outside the homogeneous trunk, and
on TPU recomputing them on every stage is cheaper than serializing the
mesh (they are a tiny fraction of FLOPs; XLA dedupes the params via
sharding anyway).

Bubble accounting: (S-1)/(vM+S-1) of trunk compute is wasted; choose
num_microbatches >= 4*S (or interleave v) to amortize — the same
guidance as the reference's 1F1B/interleave pair.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ...core.tensor import Parameter, Tensor
from ...nn.container import Sequential
from ...nn.layer import Layer
from .. import topology


class LayerDesc:
    """Deferred layer construction (≈ pp_layers.py:56 `LayerDesc`)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """≈ pp_layers.py `SharedLayerDesc`: same weights used at several
    pipeline positions (embedding/lm-head tying). In the SPMD design the
    pre/post segments are replicated over pp, so sharing is reusing one
    built Layer at each position; only the FIRST occurrence registers the
    parameters — later ones hold an unregistered reference so state_dict
    stays duplicate-free."""

    def __init__(self, key, layer_cls, *args, forward_func=None, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.key = key
        self.forward_func = forward_func


class _ForwardAdapter(Layer):
    """Run `fn(inner, *args)`. The FIRST occurrence of a shared layer
    registers it (owns its params); later occurrences hold an unregistered
    reference — under functional_call the shared values flow through the
    owning name, so state_dict stays duplicate-free."""

    def __init__(self, inner: Layer, fn: Optional[Callable],
                 owns_inner: bool = False):
        super().__init__()
        if owns_inner:
            self.inner = inner  # registered sublayer: params live here
        self._inner_ref = [inner]  # plain list: not a registered sublayer
        self._fn = fn

    def forward(self, *args, **kwargs):
        inner = self._inner_ref[0]
        if self._fn is None:
            return inner(*args, **kwargs)
        return self._fn(inner, *args, **kwargs)


def _param_shape_tree(layer: Layer):
    return tuple((name, tuple(t.shape), str(t.dtype))
                 for name, t in layer.state_dict().items())


def _find_trunk(layers: List[Layer]):
    """Longest contiguous run of structurally-identical layers = the
    pipeline trunk (the analog of the reference's uniform segmentation,
    pp_layers.py:206 `_segment_network` with seg_method='uniform').
    Identity = (class, param shapes/dtypes, repr) — repr catches
    non-parameter config differences (activation choice, epsilon, dropout
    rate) that shapes alone would miss, since all stages execute through
    the stage-0 template's forward."""
    n = len(layers)
    sigs = [(type(l), _param_shape_tree(l), repr(l)) for l in layers]
    best = (0, 0)  # (start, length)
    i = 0
    while i < n:
        j = i
        while j < n and sigs[j] == sigs[i]:
            j += 1
        if j - i > best[1]:
            best = (i, j - i)
        i = j
    start, length = best
    return start, start + length


def _sanitize(name: str) -> str:
    return name.replace(".", "__")


class PipelineLayer(Layer):
    """Partition a layer list into [pre | homogeneous trunk | post] and run
    the trunk as an SPMD collective pipeline over the 'pp' mesh axis.

    Parameters of the trunk are stored STACKED with a leading
    `num_stages`-dim carrying spec `P('pp', *block_spec)`; pre/post params
    keep their own specs (replicated over pp). The model therefore drops
    straight into `fleet.DistributedTrainStep` — no wrapper classes, no
    P2P plumbing.
    """

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None,
                 loss_fn: Optional[Callable] = None,
                 num_microbatches: Optional[int] = None,
                 use_recompute: bool = False, topology_=None,
                 interleave: int = 1,
                 seg_sizes: Optional[Sequence[int]] = None):
        super().__init__()
        shared: Dict[str, Layer] = {}
        seen: set = set()
        built: List[Layer] = []
        for d in layers:
            if isinstance(d, SharedLayerDesc):
                if d.key not in shared:
                    shared[d.key] = LayerDesc.build(d)
                layer = shared[d.key]
                first = id(layer) not in seen
                if not first or d.forward_func is not None:
                    # first occurrence owns (registers) the shared params
                    layer = _ForwardAdapter(layer, d.forward_func,
                                            owns_inner=first)
                seen.add(id(shared[d.key]))
            elif isinstance(d, LayerDesc):
                layer = d.build()
            else:
                layer = d
                if id(layer) in seen:
                    layer = _ForwardAdapter(layer, None)
                seen.add(id(d))
            built.append(layer)
        if num_stages is None:
            hcg = topology.get_hybrid_communicate_group()
            num_stages = (hcg.get_pipe_parallel_world_size()
                          if hcg is not None else 1)
        self.num_stages = int(num_stages)
        self.interleave = int(interleave)
        if self.interleave < 1:
            raise ValueError(f"interleave must be >= 1, got {interleave}")
        self.loss_fn = loss_fn
        self.num_microbatches = num_microbatches
        self.use_recompute = use_recompute

        t0, t1 = _find_trunk(built)
        trunk = built[t0:t1]
        S, v = self.num_stages, self.interleave
        U = S * v  # virtual units, traversal order u = chunk*S + stage
        if S > 1:
            if seg_sizes is not None:
                seg_sizes = [int(s) for s in seg_sizes]
                if len(seg_sizes) != U or sum(seg_sizes) != len(trunk):
                    raise ValueError(
                        f"seg_sizes {seg_sizes} must have {U} entries "
                        f"summing to the trunk length {len(trunk)}")
                if any(s < 0 for s in seg_sizes):
                    raise ValueError("seg_sizes entries must be >= 0")
            else:
                # uniform with remainder to the FIRST units (the
                # reference's seg_method='uniform' segmentation)
                base_n, rem = divmod(len(trunk), U)
                seg_sizes = [base_n + (1 if u < rem else 0)
                             for u in range(U)]
                if base_n == 0 and rem == 0:
                    raise ValueError("empty trunk cannot be pipelined")
        self.seg_sizes = seg_sizes

        self.pre = Sequential(*built[:t0])
        self.post = Sequential(*built[t1:])

        # template holds the block structure; its param VALUES are never
        # used after stacking. Plain-list stash avoids sublayer
        # registration (stacked Parameters below are the real state).
        self._block_template = [trunk[0] if trunk else Sequential()]
        self._block_state_names = (
            list(trunk[0].state_dict().keys()) if trunk else [])

        # stack every block's params/buffers -> [S, v, maxB, ...] with
        # the stage dim sharded P('pp'); padding blocks (unbalanced
        # units) reuse block 0's values and are masked in the inner scan
        self._stacked_names: Dict[str, str] = {}
        if S > 1:
            maxB = max(seg_sizes) if seg_sizes else 1
            self._max_blocks = maxB
            offs = np.concatenate([[0], np.cumsum(seg_sizes)])
            tmpl_state = trunk[0].state_dict()
            param_names = {n for n, _ in trunk[0].named_parameters()}
            for name in self._block_state_names:
                rows = []
                for s in range(S):
                    chunk_rows = []
                    for c in range(v):
                        u = c * S + s
                        blocks = trunk[offs[u]:offs[u + 1]]
                        vals = [b.state_dict()[name]._data
                                for b in blocks]
                        while len(vals) < maxB:  # padding (masked off)
                            vals.append(tmpl_state[name]._data)
                        chunk_rows.append(jnp.stack(vals, axis=0))
                    rows.append(jnp.stack(chunk_rows, axis=0))
                stacked = jnp.stack(rows, axis=0)  # [S, v, maxB, ...]
                base = getattr(tmpl_state[name], "spec", P())
                spec = P("pp", None, None, *tuple(base))
                reg = _sanitize("block_stack." + name)
                self._stacked_names[name] = reg
                if name in param_names:
                    p = Parameter(stacked)
                    p.spec = spec
                    self.add_parameter(reg, p)
                else:
                    t = Tensor(stacked)
                    t.spec = spec
                    self.register_buffer(reg, t)
            # per-[stage, chunk] real-block counts, rides shard_map
            self._seg_counts = np.array(
                [[seg_sizes[c * S + s] for c in range(v)]
                 for s in range(S)], dtype=np.int32)
        else:
            # degenerate: single stage, keep the trunk as a sublayer
            self.stage0 = Sequential(*trunk)

    # ------------------------------------------------------------------ util
    def _microbatches(self, batch: int) -> int:
        m = self.num_microbatches or max(self.num_stages, 1)
        if batch % m != 0:
            raise ValueError(f"batch {batch} not divisible by "
                             f"num_microbatches {m}")
        return m

    def _unit_call(self, names, pstacks: Sequence[jax.Array], cnt,
                   x: jax.Array):
        """Apply one unit = inner scan over its <= maxB blocks; padding
        blocks (j >= cnt) pass the activation through unchanged."""
        from ...jit.api import functional_call
        block = self._block_template[0]

        def block_body(pvals, arr):
            return functional_call(
                block, {k: v for k, v in zip(names, pvals)},
                Tensor(arr))._data

        if self.use_recompute and self.training:
            block_body = jax.checkpoint(
                block_body,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)

        def step(arr, sl):
            pvals, j = sl
            out = block_body(pvals, arr)
            return jnp.where(j < cnt, out, arr), None

        x, _ = jax.lax.scan(
            step, x, (list(pstacks), jnp.arange(pstacks[0].shape[0])))
        return x

    @staticmethod
    def _run_segment(seg: Sequential, *inputs):
        """Run a pre/post segment; the FIRST layer receives all inputs
        (e.g. (input_ids, attn_mask)), the rest chain single-activation."""
        layers = list(seg._sub_layers.values())
        if not layers:
            return inputs[0] if len(inputs) == 1 else inputs
        x = layers[0](*inputs)
        for layer in layers[1:]:
            x = layer(x)
        return x

    # --------------------------------------------------------------- forward
    def forward(self, *inputs):
        x = self._run_segment(self.pre, *inputs)
        if self.num_stages <= 1:
            x = self.stage0(x)
            return self.post(x)

        mesh = topology.get_mesh()
        if mesh is None or mesh.shape.get("pp", 1) != self.num_stages:
            raise RuntimeError(
                f"PipelineLayer needs an active mesh with pp="
                f"{self.num_stages}; call fleet.init first")

        raw = x._data if isinstance(x, Tensor) else x
        b = raw.shape[0]
        m = self._microbatches(b)
        if self.interleave > 1 and m < self.num_stages:
            raise ValueError(
                f"interleaved pipeline needs num_microbatches ({m}) >= "
                f"num_stages ({self.num_stages}) so a chunk's output has "
                f"left the ring before its next chunk enters")
        mb = raw.reshape((m, b // m) + raw.shape[1:])

        names = list(self._stacked_names.keys())
        regs = [self._stacked_names[n] for n in names]
        state = self.state_dict()
        stacked_vals = [state[r]._data for r in regs]
        # shard_map specs mention ONLY the manual 'pp' axis (leading stage
        # dim); mp/dp shardings on the other dims remain in auto mode and
        # ride along on the arrays' NamedShardings.
        specs = [P("pp") for _ in regs]

        out = _spmd_pipeline(
            self._unit_call, names, stacked_vals, specs,
            jnp.asarray(self._seg_counts), mb, mesh,
            self.num_stages, self.interleave)
        out = out.reshape((b,) + out.shape[2:])
        return self.post(Tensor(out) if isinstance(x, Tensor) else out)


def _spmd_pipeline(unit_call, names, stacked_vals, specs, seg_counts,
                   mb, mesh, num_stages: int, interleave: int = 1):
    """The collective circular-pipeline loop.

    Schedule (the SPMD form of pipeline_parallel.py:117 1F1B and :457
    interleave): virtual microbatch k = chunk*M + mu flows chunk-major
    through the S-stage ring; device s at tick t works on k = t - s with
    its chunk-(k // M) unit. Chunk c's input for mu is chunk c-1's
    output, which left stage S-1 at tick (k - M) + S - 1 <= t - 1 (needs
    M >= S) and was banked in stage 0's `inter` buffer on arrival.
    Ticks = v*M + S - 1, so the bubble is (S-1)/(vM+S-1)."""
    S = num_stages
    v = interleave
    M = mb.shape[0]
    steps = v * M + S - 1
    ring = [(i, (i + 1) % S) for i in range(S)]

    def per_device(mb_local, cnt_local, *param_slices):
        stage = jax.lax.axis_index("pp")
        # shard_map gives each device a [1, v, maxB, ...] slice
        stacks = [val[0] for val in param_slices]   # [v, maxB, ...]
        cnts = cnt_local[0]                         # [v]

        def tick(carry, t):
            # `inter` (chunk c-1 outputs banked for chunk c's entry) is
            # carried only when interleaving — at v=1 it would be an
            # extra full-microbatch HBM buffer that is provably never
            # read
            if v > 1:
                act, inter, outs = carry
                # bank the ring arrival (stage S-1's tick t-1 output) —
                # only stage 0 ever reads it, as chunk c>0 input
                k_arr = t - S
                mu_arr = jnp.clip(k_arr, 0, v * M - 1) % M
                bank = (k_arr >= 0) & (k_arr // M < v - 1)
                inter = jnp.where(
                    bank,
                    jax.lax.dynamic_update_index_in_dim(inter, act,
                                                        mu_arr, 0),
                    inter)
            else:
                act, outs = carry

            k = t - stage
            valid = (k >= 0) & (k < v * M)
            kc = jnp.clip(k, 0, v * M - 1)
            c = kc // M
            mu = kc % M
            feed = jax.lax.dynamic_index_in_dim(mb_local, mu, 0,
                                                keepdims=False)
            if v > 1:
                feedc = jax.lax.dynamic_index_in_dim(inter, mu, 0,
                                                     keepdims=False)
                feed = jnp.where(c == 0, feed, feedc)
            inp = jnp.where(stage == 0, feed, act)
            pstacks = [jax.lax.dynamic_index_in_dim(sv, c, 0,
                                                    keepdims=False)
                       for sv in stacks]
            out = unit_call(names, pstacks, cnts[c], inp)
            is_final = (stage == S - 1) & valid & (c == v - 1)
            outs = jnp.where(
                is_final,
                jax.lax.dynamic_update_index_in_dim(outs, out, mu, 0),
                outs)
            act = jax.lax.ppermute(out, "pp", ring)
            return ((act, inter, outs) if v > 1 else (act, outs)), None

        carry0 = (jnp.zeros_like(mb_local[0]), jnp.zeros_like(mb_local),
                  jnp.zeros_like(mb_local)) if v > 1 else             (jnp.zeros_like(mb_local[0]), jnp.zeros_like(mb_local))
        init = jax.lax.pcast(carry0, ("pp",), to="varying")
        final_carry, _ = jax.lax.scan(tick, init, jnp.arange(steps))
        outs = final_carry[-1]
        # [1, M, mb, ...] local -> global leading dim S over 'pp'; only
        # stage S-1's slice is real, sliced out by the caller.
        return outs[None]

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P("pp")) + tuple(specs),
        out_specs=P("pp"),
        axis_names={"pp"})
    all_stage_outs = fn(mb, seg_counts, *stacked_vals)
    return all_stage_outs[S - 1]


class PipelineParallel(Layer):
    """API-parity wrapper (≈ meta_parallel/pipeline_parallel.py:117
    `PipelineParallel` with `train_batch`). Thin: scheduling lives in the
    compiled program, so this only carries the train-step plumbing."""

    def __init__(self, layers: PipelineLayer, hcg=None, strategy=None):
        super().__init__()
        self.pipe = layers

    def forward(self, *inputs):
        return self.pipe(*inputs)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """One pipelined optimization step; `data=(inputs, labels)`.
        ≈ PipelineParallel.train_batch -> forward_backward_pipeline.
        An *enabled* GradScaler is rejected: on TPU the bf16 path needs no
        loss scaling (pass GradScaler(enable=False) for API parity)."""
        if scaler is not None and scaler.is_enable():
            raise NotImplementedError(
                "PipelineParallel.train_batch does not support an enabled "
                "GradScaler; use bf16 (no scaling) on TPU")
        from ..fleet.train_step import DistributedTrainStep
        if getattr(self, "_step_opt_id", None) != id(optimizer):
            loss_fn = self.pipe.loss_fn or (lambda o, l: o)
            self._step = DistributedTrainStep(self.pipe, optimizer, loss_fn)
            self._step_opt_id = id(optimizer)
        inputs, labels = data
        return self._step(inputs, labels)

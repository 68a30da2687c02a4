"""Operations and bytes that the algorithms need, from shapes alone.

The yardstick for every roofline share and every ``mfu``: the program's
own counts (``flops_per_token``, ``cost_analysis``) are not used, so a PR
cannot move its utilisation by changing how it counts.  Recomputation is
never counted: a backward pass is charged the matrix products the
mathematics needs (two per forward product), not what a kernel redoes.
"""
from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     dtype: str = "bfloat16") -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_flops = flops / peaks["flops_per_s"][dtype]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")


# ------------------------------------------------------------- attention

def flash_forward(batch, seq, heads, head_dim, causal=True, itemsize=2):
    """(flops, bytes) of one attention forward: QK^T and PV, 2*s*s*d
    multiply-adds each per head, halved under a causal mask; reads q, k,
    v, writes o."""
    flops = 4.0 * batch * heads * seq * seq * head_dim \
        * (0.5 if causal else 1.0)
    nbytes = 4 * batch * seq * heads * head_dim * itemsize
    return flops, nbytes


def flash_backward(batch, seq, heads, head_dim, causal=True, itemsize=2):
    """(flops, bytes) of the backward pass: dV, dP, dQ, dK, four products
    for the forward's two (the recomputed QK^T is not counted); reads q,
    k, v, o, do, writes dq, dk, dv."""
    f, _ = flash_forward(batch, seq, heads, head_dim, causal, itemsize)
    nbytes = 8 * batch * seq * heads * head_dim * itemsize
    return 2 * f, nbytes


def paged_decode_row(kv_len, hidden, itemsize=2):
    """(flops, bytes) of one decode-attention row in one layer: q.K^T and
    p.V over ``kv_len`` cached positions, kv_len*hidden multiply-adds each,
    so 4*kv_len*hidden FLOPs; reads the valid K and V (q and o are one
    position and are left out)."""
    return 4 * kv_len * hidden, 2 * kv_len * hidden * itemsize


# ------------------------------------------------------------ whole model

def block_matmul_params(cfg: dict) -> int:
    """Weights of one block's four matrix products (qkv, out, fc1, fc2)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * h * h + 2 * h * f


def forward_flops(cfg: dict, new_tokens: int, attended: float,
                  head_positions: int) -> float:
    """FLOPs of a forward pass: ``new_tokens`` positions through every
    block's matrix products, ``attended`` = sum over new positions of the
    positions each attends to (causal: s*(s+1)/2 for a prompt of s; kv_len
    for a decode step), and the output head at ``head_positions``."""
    n, h, v = cfg["num_layers"], cfg["hidden_size"], cfg["vocab_size"]
    return (2.0 * block_matmul_params(cfg) * n * new_tokens
            + 4.0 * h * n * attended
            + 2.0 * h * v * head_positions)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward of next-token training on [batch, seq]: three
    times the forward (one product forward, two backward), causal
    attention, the head at seq - 1 positions per row."""
    fwd = forward_flops(cfg, batch * seq, batch * seq * (seq + 1) / 2.0,
                        batch * (seq - 1))
    return 3.0 * fwd

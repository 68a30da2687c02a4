"""Operations and bytes of a sparse-expert decoder served by block
diffusion, from shapes alone (``work.py``'s rules: the program's own
counts are not used, recomputation is never counted).

Everything is per *position-forward*: one position of one forward pass
through the ACTIVE parameters (attention projections, the router, the
``num_experts_per_tok`` experts a token is sent to), not through all the
experts the chip holds.
"""
from __future__ import annotations


def active_matmul_params(cfg: dict) -> int:
    """Weights one position passes through in one layer: q, k, v, o, the
    router, and gate + up + down of each chosen expert."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * h * hq * d + 2 * h * hk * d
    return attn + h * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * 3 * h * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, positions: float, attended: float,
                  head_positions: float) -> float:
    """FLOPs of ``positions`` position-forwards, ``attended`` = the sum
    over them of the positions each attends to, and the output head at
    ``head_positions`` of them."""
    n, h, v = cfg["num_hidden_layers"], cfg["hidden_size"], \
        cfg["vocab_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * active_matmul_params(cfg) * n * positions
            + 4.0 * width * n * attended
            + 2.0 * h * v * head_positions)


def block_causal_attended(p: int, block: int) -> float:
    """Sum over a prompt's ``p`` positions (whole blocks) of what each
    sees under the block-causal mask: all of its own block and before."""
    nb = p // block
    return block * block * nb * (nb + 1) / 2.0


def schedule(cfg: dict) -> tuple:
    """(forwards a block needs, of them denoise steps) under the static
    schedule: ``denoising_steps`` and the commit."""
    s = int(cfg["serve"]["block_diffusion"]["denoising_steps"])
    return s + 1, s


def token_work(cfg: dict, kv_start: float) -> tuple:
    """(position-forwards, attended, head positions) that ONE output token
    costs when its block starts at cache length ``kv_start``: each of the
    block's B positions takes part in every forward of the block, so a
    token is charged ``forwards`` position-forwards, each attending the
    cache and the block; the head is needed in the denoise steps only."""
    block = int(cfg["serve"]["block_diffusion"]["block_length"])
    fwd, den = schedule(cfg)
    return fwd, fwd * (kv_start + block), den


def expert_layer(rows: int, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one dropless expert layer over ``rows`` (token,
    expert) rows: three products of 2*H*F multiply-adds a row, and the
    weights of every expert hit, read once (``rows`` well above the
    expert count hit every expert: 4,096 rows over 128 leave one out once
    in e**32 steps), plus the rows in and out."""
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    hit = min(e, rows)
    return 6.0 * rows * h * f, \
        (hit * 3 * h * f + 2 * rows * h) * itemsize


def block_attention(attended: float, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of the block attention of one layer, ``attended`` =
    the sum over lane-forwards of the cache length each attends (the B new
    positions included): q.K^T and p.V for B queries of every query head,
    and the valid K and V of the kv heads, read once a lane."""
    block = int(cfg["serve"]["block_diffusion"]["block_length"])
    d = cfg["head_dim"]
    return 4.0 * block * attended * cfg["num_attention_heads"] * d, \
        2.0 * attended * cfg["num_key_value_heads"] * d * itemsize

"""Operations and bytes of a Nemotron-H decoder (single-mixer blocks of
Mamba-2, attention and expert layers) served autoregressively as ONE
CHIP'S SHARE, from shapes alone (``work.py``'s rules: the program's own
counts are not used, recomputation is never counted).

Blocks are of different kinds, so every count is a sum over the blocks
by kind.  Work is per *position*: one position of one forward pass
through what THIS CHIP computes for it: a mixer's projections, the
router over all the experts it ranks, the shared expert whole, and the
routed experts of the chip's share a token is sent to on average,
``num_experts_per_tok x n_routed_experts / router_experts`` (6 x 64 /
128 = 3), not all the experts the chip holds and not the ones it does
not.
"""
from __future__ import annotations

#: multiply-adds of the state update a state element: decay, outer
#: product, accumulate, times C, sum
SSM_FLOPS_PER_ELEMENT = 5


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def state_elements(cfg: dict) -> int:
    """Elements of one lane's SSM state in one layer: heads x P x N."""
    return d_inner(cfg) * cfg["ssm_state_size"]


def layer_counts(cfg: dict) -> dict:
    """How many blocks are a Mamba-2 mixer, an attention mixer, an
    expert layer."""
    pattern = cfg["hybrid_override_pattern"]
    return {"ssm": pattern.count("M"), "attn": pattern.count("*"),
            "moe": pattern.count("E")}


def routed_here(cfg: dict) -> float:
    """Routed experts of this chip's share a token is sent to, on
    average."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg.get("router_experts", cfg["n_routed_experts"])


def active_matmul_params(cfg: dict) -> float:
    """Weights one position passes through on this chip in the whole
    trunk: in and out projections of a Mamba-2 block (H -> d_inner +
    conv_dim + heads, d_inner -> H); q, k, v, o of an attention block;
    the router, the shared expert's up and down and ``routed_here``
    ungated experts' up and down of an expert block."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = layer_counts(cfg)
    ssm = h * (d_inner(cfg) + conv_dim(cfg) + cfg["mamba_num_heads"]) \
        + d_inner(cfg) * h
    attn = 2 * h * hq * d + 2 * h * hk * d
    moe = h * cfg.get("router_experts", cfg["n_routed_experts"]) \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"] \
        + routed_here(cfg) * 2 * h * cfg["moe_intermediate_size"]
    return n["ssm"] * ssm + n["attn"] * attn + n["moe"] * moe


def forward_flops(cfg: dict, new_tokens: float, attended: float,
                  head_positions: float) -> float:
    """FLOPs of a forward pass (``work.forward_flops``'s signature, which
    ``serve_loop`` calls): ``new_tokens`` positions through the active
    weights, the convolutions' taps (2 * K * conv_dim a position and
    Mamba-2 block) and the state update (``SSM_FLOPS_PER_ELEMENT`` a
    state element), ``attended`` = the sum over new positions of the
    positions each attends (in the ATTENTION blocks only), and the head
    over the vocabulary's slice at ``head_positions``."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n = layer_counts(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    ssm = 2.0 * cfg["conv_kernel"] * conv_dim(cfg) \
        + SSM_FLOPS_PER_ELEMENT * state_elements(cfg)
    return ((2.0 * active_matmul_params(cfg) + ssm * n["ssm"]) * new_tokens
            + 4.0 * width * n["attn"] * attended
            + 2.0 * h * v * head_positions)


def expert_layer(rows: int, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of ONE dropless expert layer's routed part over
    ``rows`` (token, expert) rows that fell on the held experts: two
    products of 2*H*F multiply-adds a row (ungated: up, down), and the
    weights of every held expert hit, read once at the PUBLISHED width
    (the program stores them padded: what that costs shows as lost
    share), plus the rows in and out.  ``layer_counts(cfg)["moe"]``
    layers have one."""
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["n_routed_experts"]
    hit = min(e, rows)
    return 4.0 * rows * h * f, (hit * 2 * h * f + 2 * rows * h) * itemsize


def decode_attention(attended: float, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of the decode attention of ONE attention block,
    ``attended`` = the sum over decoded tokens of the cache length each
    attends: q.K^T and p.V for one query of every query head, and the
    valid K and V of the kv heads read once (q and o are one position
    and are left out).  ``layer_counts(cfg)["attn"]`` blocks have one."""
    d = cfg["head_dim"]
    return 4.0 * attended * cfg["num_attention_heads"] * d, \
        2.0 * attended * cfg["num_key_value_heads"] * d * itemsize


def ssm_update(lane_steps: float, cfg: dict, itemsize: int = 4) -> tuple:
    """(flops, bytes) of the one-step state update of ONE Mamba-2 block
    over ``lane_steps`` (live lane, step) pairs: each reads and writes
    its ``[heads, P, N]`` float32 state once.  ``layer_counts(cfg)["ssm"]``
    blocks have one."""
    n = state_elements(cfg)
    return float(SSM_FLOPS_PER_ELEMENT) * lane_steps * n, \
        2.0 * lane_steps * n * itemsize

"""Operations and bytes of a hybrid decoder (short-convolution and
attention mixers, dense and sparse-expert MLPs) served autoregressively,
from shapes alone (``work.py``'s rules: the program's own counts are not
used, recomputation is never counted).

Layers are of different kinds, so every count is a sum over the layers
by kind.  Work is per *position*: one position of one forward pass
through the ACTIVE parameters (a mixer's projections, the dense MLP or
the router and the ``num_experts_per_tok`` experts a token is sent to),
not through all the experts the chip holds.
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_counts(cfg: dict) -> dict:
    """How many layers have an attention mixer, a convolution mixer, a
    dense MLP, an expert MLP."""
    kinds = cfg["layer_types"]
    attn = sum(k == "full_attention" for k in kinds)
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {"attn": attn, "conv": len(kinds) - attn, "dense": dense,
            "moe": len(kinds) - dense}


def active_matmul_params(cfg: dict) -> int:
    """Weights one position passes through in the whole trunk: q, k, v, o
    of an attention layer; in and out projections of a convolution layer
    (H -> 3H, H -> H); gate, up, down of a dense MLP; the router and gate
    + up + down of each chosen expert of an expert MLP."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = layer_counts(cfg)
    attn = 2 * h * hq * d + 2 * h * hk * d
    conv = 4 * h * h
    dense = 3 * h * cfg["intermediate_size"]
    moe = h * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * h \
        * cfg["moe_intermediate_size"]
    return n["attn"] * attn + n["conv"] * conv + n["dense"] * dense \
        + n["moe"] * moe


def forward_flops(cfg: dict, new_tokens: float, attended: float,
                  head_positions: float) -> float:
    """FLOPs of a forward pass (``work.forward_flops``'s signature, which
    ``serve_loop`` calls): ``new_tokens`` positions through the active
    weights and the convolutions' ``conv_L_cache`` taps (2 * L * H a
    position and convolution layer), ``attended`` = the sum over new
    positions of the positions each attends (in the ATTENTION layers
    only), and the tied head at ``head_positions``."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n = layer_counts(cfg)
    width = cfg["num_attention_heads"] * head_dim(cfg)
    return ((2.0 * active_matmul_params(cfg)
             + 2.0 * cfg["conv_L_cache"] * h * n["conv"]) * new_tokens
            + 4.0 * width * n["attn"] * attended
            + 2.0 * h * v * head_positions)


def expert_layer(rows: int, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of ONE dropless expert layer over ``rows`` (token,
    expert) rows: three products of 2*H*F multiply-adds a row, and the
    weights of every expert hit, read once, plus the rows in and out
    (``work_moe.expert_layer``'s rule).  ``layer_counts(cfg)["moe"]``
    layers have one."""
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    hit = min(e, rows)
    return 6.0 * rows * h * f, \
        (hit * 3 * h * f + 2 * rows * h) * itemsize


def decode_attention(attended: float, cfg: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of the decode attention of ONE attention layer,
    ``attended`` = the sum over decoded tokens of the cache length each
    attends: q.K^T and p.V for one query of every query head, and the
    valid K and V of the kv heads read once (q and o are one position
    and are left out).  ``layer_counts(cfg)["attn"]`` layers have one."""
    d = head_dim(cfg)
    return 4.0 * attended * cfg["num_attention_heads"] * d, \
        2.0 * attended * cfg["num_key_value_heads"] * d * itemsize

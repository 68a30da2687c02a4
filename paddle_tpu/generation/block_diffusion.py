"""Generation by diffusion over blocks (SDAR-style block diffusion).

A request's output grows a BLOCK of ``block_length`` positions at a time.
A block opens all masked (the mask id ``M``); a **denoise step** is one
forward of the block's positions — they see the committed cache before
the block and each other — after which the most confident masked
positions take their argmax token; once no position is masked a **commit
step** forwards the now final block once more, which writes its K and V,
and the next block opens. So a lane yields 0, 1 or several tokens a step,
out of order inside its block, and a block of B costs
``denoising_steps + 1`` forwards under the static schedule.

This module holds the per-lane block state and the step's arithmetic; the
serving engine builds its ``step`` program from :func:`apply_block_step`
as it builds the speculative one from ``speculative.apply_verify_window``.
Denoise or commit is DATA, not shape: one program serves lanes in every
phase.

Per-lane state (``[batch]`` lanes of the engine, all on the device):

* ``blk`` [batch, B]: the block's current ids, ``M`` where still masked;
* ``blk_step`` [batch]: denoise steps this block has had;
* ``out0`` [batch]: the output index of the block's first position
  (negative in a first block that opens with the prompt's ``n mod B``
  left-over tokens);
* the cache's ``kv_len``: the block's first absolute position;
* ``steps`` [batch]: output tokens unmasked so far (the lane's progress),
  ``budget`` [batch], ``out_buf`` / ``ustep_buf`` [batch, cap]: the tokens
  and, for each, the denoise step of its block at which it was unmasked.

Rules (the plain reference ``benchmarks/reference/sdar.py`` states the
same): the mask id's logit is ``-inf``; confidence is the softmax
probability of the argmax; ``low_confidence_static`` unmasks the
``ceil(B / denoising_steps)`` most confident candidates,
``low_confidence_dynamic`` every candidate above the threshold and the
most confident one always; ties go to the earlier position; positions of
the last block beyond the budget stay masked and are no candidates; a
lane finishes when every position inside its budget is unmasked (its last
block needs no commit: nothing reads it).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BlockDiffusionConfig", "as_block_diffusion_config",
           "first_block", "apply_block_step"]

_REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """Static block-diffusion knobs (hashable: a jit static argument).

    block_length: positions a block holds; one decode window, so at most
    the decode kernels' ``MAX_DECODE_QLEN``, and a divisor of 128 (the
    block-causal prefill kernel's blocks hold whole mask blocks).
    denoising_steps: the static schedule unmasks
    ``ceil(block_length / denoising_steps)`` positions a step.
    remasking: ``"low_confidence_static"`` or ``"low_confidence_dynamic"``.
    confidence_threshold: the dynamic rule's tau (a probability).
    mask_token_id: the id a masked position holds."""
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token_id: int = 0

    def __post_init__(self):
        from ..kernels.flash_attention import MAX_DECODE_QLEN
        b = self.block_length
        if not 1 <= b <= MAX_DECODE_QLEN or 128 % b:
            raise ValueError(
                f"block_length {b}: a divisor of 128 no larger than the "
                f"decode window MAX_DECODE_QLEN ({MAX_DECODE_QLEN})")
        if not 1 <= self.denoising_steps <= b:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} outside "
                f"[1, block_length {b}]")
        if self.remasking not in _REMASKING:
            raise ValueError(f"remasking {self.remasking!r}: one of "
                             f"{_REMASKING}")
        if not 0.0 < self.confidence_threshold <= 1.0:
            raise ValueError("confidence_threshold must lie in (0, 1]")
        if self.mask_token_id < 0:
            raise ValueError("mask_token_id must be a token id")

    @property
    def unmask_per_step(self) -> int:
        return -(-self.block_length // self.denoising_steps)


def as_block_diffusion_config(block_diffusion):
    """Coerce the user-facing ``block_diffusion=`` argument (None | dict
    of the fields | BlockDiffusionConfig)."""
    if block_diffusion is None or block_diffusion is False:
        return None
    if isinstance(block_diffusion, dict):
        return BlockDiffusionConfig(**block_diffusion)
    if not isinstance(block_diffusion, BlockDiffusionConfig):
        raise TypeError(
            "block_diffusion= takes a dict of BlockDiffusionConfig's "
            f"fields or one; got {type(block_diffusion).__name__}")
    return block_diffusion


def first_block(prompt: np.ndarray, bd: BlockDiffusionConfig):
    """Host side of an admission: ``(whole, blk, out0)`` — the count of
    prompt tokens in whole blocks (what the prefill commits), the first
    generated block's ids (the ``n mod B`` left-over prompt tokens, then
    ``M``), and its ``out0`` = ``-(n mod B)``."""
    b = bd.block_length
    n = int(prompt.size)
    whole = n // b * b
    blk = np.full((b,), bd.mask_token_id, np.int32)
    blk[:n - whole] = prompt[whole:]
    return whole, blk, whole - n


def _candidates(blk, out0, budget, bd):
    """[batch, B] bool: masked, and inside the lane's budget."""
    j = jnp.arange(bd.block_length, dtype=jnp.int32)[None, :]
    return (blk == bd.mask_token_id) & (out0[:, None] + j < budget[:, None])


def select_unmask(conf, cand, bd):
    """Which candidates a denoise step unmasks ([batch, B] bool); ``conf``
    is the log-probability of each position's argmax. Ties go to the
    earlier position (``top_k`` and ``argmax`` both keep the first)."""
    c = jnp.where(cand, conf, -jnp.inf)
    if bd.remasking == "low_confidence_static":
        k = bd.unmask_per_step
        _, idx = jax.lax.top_k(c, k)
        rows = jnp.arange(c.shape[0])[:, None]
        pick = jnp.zeros(c.shape, bool).at[rows, idx].set(True)
    else:
        best = jnp.argmax(c, axis=1)
        pick = (c > math.log(bd.confidence_threshold)) \
            | jax.nn.one_hot(best, c.shape[1], dtype=bool)
    return pick & cand


def commits_now(cand_before, cand_after):
    """[batch] bool: lanes whose step is a COMMIT (the forward just run
    saw a block with no candidate left, so the K and V it wrote are the
    final block's). ``cand_after`` is what the block would hold after
    this step's unmasking; the sound rule does not look at it."""
    del cand_after
    return ~jnp.any(cand_before, axis=1)


def apply_block_step(logits, bd, cache, kv0, finished, steps, budget,
                     out_buf, ustep_buf, blk, blk_step, out0, counters):
    """One step of every lane after the forward of ``blk``.

    ``logits`` [batch, B, vocab] float32 at the block's positions;
    ``cache`` as the forward left it (K and V of the window written at
    ``kv0``, its ``kv_len`` is overwritten here); ``counters`` [3] int32:
    lane-forwards, tokens unmasked, commits (lifetime, drained by the
    poll). Returns the new ``(cache, finished, steps, out_buf, ustep_buf,
    blk, blk_step, out0, counters)``; ``budget`` does not change."""
    B = bd.block_length
    live = ~finished
    cand = _candidates(blk, out0, budget, bd)
    logits = logits.at[:, :, bd.mask_token_id].set(-jnp.inf)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    pick = select_unmask(conf, cand, bd) & live[:, None]
    new_blk = jnp.where(pick, x0, blk)
    commit = commits_now(cand, cand & ~pick) & live

    # the unmasked tokens become output, each with the step that made it
    rows = jnp.arange(blk.shape[0], dtype=jnp.int32)[:, None]
    idx = out0[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    idx = jnp.where(pick, idx, out_buf.shape[1])       # else: dropped
    out_buf = out_buf.at[rows, idx].set(x0, mode="drop")
    ustep_buf = ustep_buf.at[rows, idx].set(
        jnp.broadcast_to(blk_step[:, None], idx.shape)
        .astype(ustep_buf.dtype), mode="drop")
    n_new = jnp.sum(pick, axis=1).astype(jnp.int32)
    steps = steps + n_new
    finished = finished | (steps >= budget)

    # a commit advances the cache past the block and opens the next one
    nxt = jnp.full_like(blk, bd.mask_token_id)
    blk = jnp.where(commit[:, None], nxt, new_blk)
    blk_step = jnp.where(commit, 0, blk_step + live.astype(jnp.int32))
    out0 = jnp.where(commit, out0 + B, out0)
    kv_len = jnp.where(commit, kv0 + B, kv0)
    # dead slots: kv_len pinned at 0, the engine's idle-lane contract
    cache = cache.with_kv_len(jnp.where(finished, 0, kv_len))
    counters = counters + jnp.stack([
        jnp.sum(live), jnp.sum(n_new), jnp.sum(commit)]).astype(jnp.int32)
    return (cache, finished, steps, out_buf, ustep_buf, blk, blk_step,
            out0, counters)

"""Speculative decoding: draft-propose + single-dispatch verify.

Sequential decode pays one target-model dispatch per token — the
latency-bound regime serving lives in at low batch. Speculation breaks
the serialization: a cheap DRAFTER proposes K tokens, then ONE target
forward over the K+1-token window (the ragged q-len 1..8 shape
``kernels.flash_attention_decode`` already supports) verifies them all,
and per-row accept lengths decide how many tokens each row really
emitted (1..K+1 per dispatch). Two drafters share the machinery:

- **self-speculative / prompt-lookup** (``mode="ngram"``): find the
  most recent earlier occurrence of the last ``ngram`` tokens in the
  row's own token buffer (prompt + everything emitted, resident on
  device) and propose its continuation. Pure jnp, no second model —
  every deployment benefits; it shines on the input-grounded repetition
  real traffic is full of (summarization, code edit, RAG).
- **draft model** (``mode="draft"``): a small LM sharing the target's
  vocab and the exact ``KVCache`` layout proposes K tokens greedily
  (one jitted program unrolls the K+1 tiny steps — the extra step
  writes the last draft token's KV so both caches stay position-aligned
  under full acceptance).

Acceptance is exact, never approximate:

- **greedy**: accept draft tokens while they equal the target argmax;
  emit the accepted prefix plus the target's own token at the first
  mismatch. The emitted stream is BITWISE the sequential greedy stream
  — the tier-1 gate asserts it on session and engine paths.
- **temperature > 0**: rejection sampling against the target's
  FILTERED distribution (temperature/top-k/top-p, the same transforms
  ``sampling.sample`` applies). Both drafters propose deterministically
  (a point-mass draft distribution), so token ``d`` is accepted with
  probability ``p_target(d)`` and a rejection resamples from the
  residual with ``d`` masked out — the emitted marginal equals
  sequential sampling exactly (tested distributionally).

KV-cache rollback is free: the verify forward writes all K+1 positions,
then per-row ``kv_len`` is rolled back to ``base + emit_n`` — entries
past ``kv_len`` are invisible to attention and overwritten by the next
window. The ring must carry ``spec.k`` slack beyond prompt+max_new for
the last window's unaccepted overhang; ``generate()`` and the engine
validate that bound up front (the clamp satellite).

Reference analog: the reference's inference layer amortizes decode
dispatch overhead with fused multi-token ops; speculative verify is the
same amortization expressed as one ragged-window program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monitor
from ..core.tensor import Tensor

__all__ = ["SpeculativeConfig", "SpeculativeSession", "ngram_propose",
           "spec_accept"]


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Static speculation knobs (hashable: a jit static argument —
    a new config compiles a new draft/verify pair).

    mode: ``"ngram"`` (self-speculative prompt lookup, no second model)
    or ``"draft"`` (a draft LM passed separately).
    k: draft tokens proposed per window; the verify window is ``k + 1``
    query rows and must fit the decode kernel's sublane tile.
    ngram: suffix length the prompt-lookup drafter matches on."""
    mode: str = "ngram"
    k: int = 4
    ngram: int = 3

    def __post_init__(self):
        from ..kernels.flash_attention import MAX_DECODE_QLEN
        if self.mode not in ("ngram", "draft"):
            raise ValueError(
                f"speculative mode {self.mode!r}: one of 'ngram' "
                "(self-speculative prompt lookup) or 'draft' (draft "
                "model)")
        if self.k < 1:
            raise ValueError(f"speculative draft_k must be >= 1, "
                             f"got {self.k}")
        if self.k + 1 > MAX_DECODE_QLEN:
            # the q-len guard at the API boundary: fail here with the
            # limit's name instead of letting an oversized window fall
            # through the decode kernel's padding paths
            raise ValueError(
                f"speculative draft_k={self.k}: the verify window "
                f"k+1={self.k + 1} exceeds flash_attention_decode's "
                f"MAX_DECODE_QLEN ({MAX_DECODE_QLEN}, the 8-row fp32 "
                f"sublane tile); use draft_k <= {MAX_DECODE_QLEN - 1}")
        if self.ngram < 1:
            raise ValueError(f"speculative ngram must be >= 1, "
                             f"got {self.ngram}")


def as_spec_config(speculative, draft_model=None):
    """Coerce the user-facing ``speculative=`` argument (None | mode
    string | SpeculativeConfig) and cross-check the draft model."""
    if speculative is None or speculative is False:
        return None
    if isinstance(speculative, str):
        speculative = SpeculativeConfig(mode=speculative)
    if not isinstance(speculative, SpeculativeConfig):
        raise TypeError(
            "speculative= takes 'ngram', 'draft', or a "
            f"SpeculativeConfig; got {type(speculative).__name__}")
    if speculative.mode == "draft" and draft_model is None:
        raise ValueError(
            "speculative='draft' needs draft_model= (a generative LM "
            "sharing the target's vocabulary); use "
            "speculative='ngram' for model-free self-speculation")
    if speculative.mode == "ngram" and draft_model is not None:
        raise ValueError(
            "draft_model= given but speculative mode is 'ngram'; pass "
            "speculative='draft' to use it")
    return speculative


# ------------------------------------------------------------- drafters

def ngram_propose(tok_buf, tok_len, *, k: int, n: int):
    """Prompt-lookup proposal, pure jnp with static shapes.

    tok_buf: [B, L] int32 — each row's full token history (prompt +
    every emitted token, INCLUDING the pending one the next window
    feeds). tok_len: [B] int32 valid lengths. Finds the most recent
    p < len - n with ``buf[p:p+n] == buf[len-n:len]`` and proposes the
    k tokens following the match (clamped to known tokens); rows with
    no match (or history shorter than n+1) propose their last token
    repeated — verification keeps correctness either way, a bad draft
    only costs accept rate."""
    b, L = tok_buf.shape
    ctx_idx = jnp.clip(tok_len[:, None] - n + jnp.arange(n)[None, :],
                       0, L - 1)
    ctx = jnp.take_along_axis(tok_buf, ctx_idx, axis=1)        # [B, n]
    # candidate windows buf[p:p+n] for every p, as [B, L-n+1, n]
    win = jnp.stack([tok_buf[:, i:L - n + 1 + i] for i in range(n)],
                    axis=-1)
    eq = jnp.all(win == ctx[:, None, :], axis=-1)              # [B, P]
    p = jnp.arange(L - n + 1, dtype=jnp.int32)[None, :]
    valid = (p < tok_len[:, None] - n) & (tok_len[:, None] >= n + 1)
    best = jnp.max(jnp.where(eq & valid, p, -1), axis=1)       # [B]
    last = jnp.take_along_axis(
        tok_buf, jnp.maximum(tok_len - 1, 0)[:, None], axis=1)[:, 0]
    cont_idx = best[:, None] + n + jnp.arange(k, dtype=jnp.int32)[None, :]
    cont = jnp.take_along_axis(tok_buf, jnp.clip(cont_idx, 0, L - 1),
                               axis=1)
    ok = (best[:, None] >= 0) & (cont_idx < tok_len[:, None])
    return jnp.where(ok, cont, last[:, None]).astype(jnp.int32)


# ----------------------------------------------------------- acceptance

def spec_accept(logits, draft, key, cfg):
    """Accept/reject K deterministic draft tokens against the target's
    K+1 logits. logits: [B, K+1, V] fp32 (position j predicts the token
    AFTER window input j); draft: [B, K] int32. Returns
    ``(emitted [B, K+1], n_accept [B])`` — emitted[j] is draft[j] for
    j < n_accept, the target's own correction/bonus token at
    j == n_accept, garbage beyond (masked by the caller's emit count).

    Greedy (cfg.do_sample False or temperature 0): accept while
    draft == argmax — the emitted stream is bitwise the sequential
    greedy stream. Sampling: rejection sampling against the FILTERED
    target distribution (temperature/top-k/top-p, exactly
    ``sampling.sample``'s transforms); the drafters are deterministic
    (point-mass q), so accept-with-prob-p(d) + residual-resample
    reproduces the sequential sampling marginal exactly."""
    from .sampling import apply_temperature, apply_top_k, apply_top_p
    b, kp1, v = logits.shape
    k = kp1 - 1
    pos = jnp.arange(kp1, dtype=jnp.int32)[None, :]
    dpad = jnp.concatenate([draft, draft[:, -1:]], axis=1)     # [B, K+1]
    if not cfg.do_sample or float(cfg.temperature) == 0.0:  # lint: host-sync-ok (static config coercion)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, K+1]
        match = (draft == tgt[:, :k]).astype(jnp.int32)
        n_accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        corr = jnp.take_along_axis(tgt, n_accept[:, None], axis=1)
        emitted = jnp.where(pos == n_accept[:, None], corr, dpad)
        return emitted.astype(jnp.int32), n_accept
    f = apply_temperature(logits, cfg.temperature)
    if cfg.top_k and cfg.top_k > 0:
        f = apply_top_k(f, cfg.top_k)
    if cfg.top_p is not None and float(cfg.top_p) < 1.0:  # lint: host-sync-ok (static config coercion)
        f = apply_top_p(f, cfg.top_p)
    probs = jax.nn.softmax(f, axis=-1)                         # [B,K+1,V]
    p_draft = jnp.take_along_axis(probs[:, :k], draft[..., None],
                                  axis=-1)[..., 0]             # [B, K]
    ku, kr = jax.random.split(key)
    accept = (jax.random.uniform(ku, (b, k)) < p_draft).astype(jnp.int32)
    n_accept = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)    # 0..K
    # distribution at the stop position: residual (draft token masked,
    # renormalized by categorical) on a rejection, the plain filtered
    # distribution for the bonus token on full acceptance
    p_stop = jnp.take_along_axis(
        probs, n_accept[:, None, None],
        axis=1)[:, 0]                                          # [B, V]
    d_stop = jnp.take_along_axis(dpad, n_accept[:, None], axis=1)[:, 0]
    masked = p_stop * (jnp.arange(v)[None, :] != d_stop[:, None])
    resid = jnp.where((n_accept == k)[:, None], p_stop, masked)
    corr = jax.random.categorical(
        kr, jnp.log(jnp.maximum(resid, 1e-38)), axis=-1).astype(jnp.int32)
    emitted = jnp.where(pos == n_accept[:, None], corr[:, None], dpad)
    return emitted.astype(jnp.int32), n_accept


def acceptance_bookkeeping(emitted, n_accept, finished, done, budget,
                           eos_token_id):
    """Clamp a window's acceptance into per-row emit counts.

    done/budget: [B] int32 tokens already emitted / per-row cap. The
    clamps are the overshoot guard: a row can never emit past its
    budget (``emit_n <= budget - done``) nor past its first eos inside
    the window. Returns ``(emit_n, new_finished)``; callers advance
    ``done``/``kv_len``/buffers by ``emit_n``."""
    kp1 = emitted.shape[1]
    avail = jnp.maximum(budget - done, 0)
    emit_n = jnp.minimum(n_accept + 1, avail)
    emit_n = jnp.where(finished, 0, emit_n)
    j = jnp.arange(kp1, dtype=jnp.int32)[None, :]
    if eos_token_id is not None:
        is_eos = (emitted == jnp.int32(eos_token_id)) & \
            (j < emit_n[:, None])
        eos_hit = jnp.any(is_eos, axis=1)
        first = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
        emit_n = jnp.where(eos_hit, jnp.minimum(emit_n, first + 1),
                           emit_n)
    else:
        eos_hit = jnp.zeros(finished.shape, bool)
    new_finished = finished | eos_hit | (done + emit_n >= budget)
    return emit_n, new_finished


def scatter_window(buf, start, vals, emit_n):
    """Write ``vals[:, :emit_n]`` into ``buf`` at per-row offsets
    ``start`` (masked lanes routed out of bounds and dropped, so a
    clamped row never writes anywhere)."""
    b, c = buf.shape
    j = jnp.arange(vals.shape[1], dtype=jnp.int32)[None, :]
    idx = jnp.where(j < emit_n[:, None], start[:, None] + j, c)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    return buf.at[rows, idx].set(vals, mode="drop")


def window_advance(tok, emitted, emit_n):
    """Next pending token: the window's last emitted token (the row's
    old pending token when the row emitted nothing)."""
    last = jnp.take_along_axis(
        emitted, jnp.maximum(emit_n - 1, 0)[:, None], axis=1)[:, 0]
    return jnp.where(emit_n > 0, last, tok).astype(jnp.int32)


def apply_verify_window(logits, draft, key, cfg, spec, tok, cache,
                        finished, done, budget, out_buf, tok_buf,
                        tok_len, proposed, accepted, *,
                        pin_finished_kv=False):
    """The one acceptance/bookkeeping core behind every verify program
    (the session's verify_fn AND the engine's fused slot step): accept
    the window, clamp emissions (budget/eos/finished), scatter into the
    output and token-history buffers, advance the pending token and
    counters, and roll the cache back to the accepted window.
    ``pin_finished_kv`` is the engine's idle-lane contract (finished
    slots hold kv_len 0 so they never wrap the ring while parked).
    Returns ``(tok, cache, finished, done, out_buf, tok_buf, tok_len,
    proposed, accepted)`` — all advanced."""
    emitted, n_accept = spec_accept(logits, draft, key, cfg)
    emit_n, new_finished = acceptance_bookkeeping(
        emitted, n_accept, finished, done, budget, cfg.eos_token_id)
    out_buf = scatter_window(out_buf, done, emitted, emit_n)
    tok_buf = scatter_window(tok_buf, tok_len, emitted, emit_n)
    live = (~finished).astype(jnp.int32)
    proposed = proposed + jnp.int32(spec.k) * jnp.sum(live)
    # clamped-away acceptances count as NOT accepted (they were wasted
    # proposals); the correction/bonus token is never a draft token
    accepted = accepted + jnp.sum(jnp.minimum(n_accept, emit_n) * live)
    tok = window_advance(tok, emitted, emit_n)
    # rollback: the forward wrote (and advanced past) all K+1 window
    # positions; keep only the accepted inputs
    base = cache.kv_len - jnp.int32(spec.k + 1)
    new_len = base + emit_n
    if pin_finished_kv:
        new_len = jnp.where(new_finished, 0, new_len)
    cache = cache.with_kv_len(new_len)
    return (tok, cache, new_finished, done + emit_n, out_buf, tok_buf,
            tok_len + emit_n, proposed, accepted)


# -------------------------------------------------------------- session

class SpeculativeSession:
    """The jitted (draft, verify) program pair over one target network
    (and, in draft mode, one draft network). Built once per
    (GenerationSession, SpeculativeConfig, draft network) and cached on
    the generation session, so jax's jit cache carries warm executables
    across ``generate(speculative=...)`` calls; ``aot_compile`` is the
    Predictor's bucket path (compile at startup, zero retraces under
    traffic, executables persisted through the ``jit.compile_cache``
    store)."""

    def __init__(self, session, spec: SpeculativeConfig,
                 draft_network=None):
        from ..jit.api import _RetraceTracker, _unwrap, functional_call
        from .api import GenerationSession, _expect_logits_cache
        self.session = session
        self.spec = spec
        self.draft_network = draft_network
        network = session.network
        names = session._names
        self._draft_tracker = _RetraceTracker()
        self._verify_tracker = _RetraceTracker()
        self._compiled = {}

        if spec.mode == "draft":
            if draft_network is None:
                raise ValueError("speculative mode 'draft' needs a "
                                 "draft network")
            draft_network.eval()
            # the draft model's own (prefill, decode) session: prefill
            # fills the draft KV cache at generate() start; its decode
            # program is unused (the draft loop below replaces it)
            self._draft_session = GenerationSession(
                draft_network, executable_store=session.executable_store)
            dnames = self._draft_session._names

            def draft_fn(dvals, tok, dcache, sync_len, spec):
                # re-anchor the draft cache at the target's accepted
                # length (the post-rollback kv_len travels as data), so
                # one program serves every acceptance outcome
                dcache = dcache.with_kv_len(sync_len)
                drafts = []
                t = tok
                # k proposals + one extra step that only writes the
                # last draft token's KV: under full acceptance the next
                # window's rollback needs base + k + 1 entries in BOTH
                # caches (the k+1'th greedy token is discarded)
                for _ in range(spec.k + 1):
                    out = functional_call(
                        draft_network, dict(zip(dnames, dvals)),
                        Tensor(t[:, None]), cache=dcache)
                    logits, dcache = _expect_logits_cache(out)
                    t = jnp.argmax(
                        _unwrap(logits)[:, -1].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
                    drafts.append(t)
                return jnp.stack(drafts[:spec.k], axis=1), dcache
        else:
            self._draft_session = None

            def draft_fn(tok_buf, tok_len, spec):
                return ngram_propose(tok_buf, tok_len, k=spec.k,
                                     n=spec.ngram)

        def verify_fn(state_vals, tok, draft, cache, key, finished,
                      done, budget, out_buf, tok_buf, tok_len, proposed,
                      accepted, cfg, spec):
            window = jnp.concatenate([tok[:, None], draft], axis=1)
            out = functional_call(network, dict(zip(names, state_vals)),
                                  Tensor(window), cache=cache)
            logits, cache = _expect_logits_cache(out)
            logits = _unwrap(logits).astype(jnp.float32)  # [B, K+1, V]
            k0, k1 = jax.random.split(key)
            (tok, cache, finished, done, out_buf, tok_buf, tok_len,
             proposed, accepted) = apply_verify_window(
                logits, draft, k0, cfg, spec, tok, cache, finished,
                done, budget, out_buf, tok_buf, tok_len, proposed,
                accepted)
            return (tok, cache, k1, finished, done, out_buf, tok_buf,
                    tok_len, proposed, accepted)

        self._draft_fn, self._verify_fn = draft_fn, verify_fn
        tpu = jax.default_backend() == "tpu"
        # donation intent (TPU only; CPU/GPU donation is a warn-only
        # no-op): every state-carrying lane of the verify step — cache,
        # pending token, key, flags, counters, and both token buffers —
        # updates in place across windows. audit() gates this intent.
        self._verify_donate = (1, 3, 4, 5, 6, 8, 9, 10, 11, 12) \
            if tpu else ()
        self._draft_donate = ((2,) if tpu else ()) \
            if spec.mode == "draft" else ()
        self._draft_jit = jax.jit(
            draft_fn,
            static_argnums=(4,) if spec.mode == "draft" else (2,),
            donate_argnums=self._draft_donate)
        self._verify_jit = jax.jit(verify_fn, static_argnums=(13, 14),
                                   donate_argnums=self._verify_donate)

    # ----------------------------------------------------------- calling
    def registered_buf_width(self, batch: int, cache_len: int, cfg,
                             min_width: int) -> int:
        """The smallest AOT-registered verify out-buffer width that can
        hold ``min_width`` tokens (or ``min_width`` itself when nothing
        matching is registered). The verify executable is shape-keyed
        on the out buffer, so a caller asking for FEWER tokens than the
        compiled budget (``Predictor.generate(max_new_tokens=...)``)
        must decode into the compiled width — budget travels as a lane,
        the program never depends on it — instead of missing every warm
        executable and re-compiling under traffic."""
        widths = [k[2][1] for k in self._compiled
                  if k[0] == "verify" and k[1] == (batch,)
                  and k[3] == cache_len and k[4] == cfg
                  and k[2][1] >= min_width]
        return min(widths) if widths else min_width

    def _draft_key(self, args):
        # ngram dispatches (tok_buf, tok_len); draft mode dispatches
        # (draft_state, tok, draft_cache, sync_len) — the shape-bearing
        # arg differs, the key shape is what AOT registered
        return ("draft", args[0].shape if self.spec.mode == "ngram"
                else args[1].shape)

    def draft(self, *args):
        """One draft dispatch: ``(tok_buf, tok_len)`` in ngram mode,
        ``(draft_state, tok, draft_cache, sync_len)`` in draft mode."""
        self.session._ensure_eval()
        exe = self._compiled.get(self._draft_key(args))
        if exe is not None:
            return exe(*args)
        pre = self._draft_tracker.pre(self._draft_jit)
        out = self._draft_jit(*args, self.spec)
        self._draft_tracker.observe(
            self._draft_jit,
            tuple(getattr(a, "shape", None) for a in args), pre)
        return out

    def verify(self, state_vals, tok, draft, cache, key, finished, done,
               budget, out_buf, tok_buf, tok_len, proposed, accepted,
               cfg):
        self.session._ensure_eval()
        ckey = ("verify", tok.shape, out_buf.shape, cache.max_len, cfg)
        exe = self._compiled.get(ckey)
        if exe is not None:
            return exe(state_vals, tok, draft, cache, key, finished,
                       done, budget, out_buf, tok_buf, tok_len,
                       proposed, accepted)
        pre = self._verify_tracker.pre(self._verify_jit)
        out = self._verify_jit(state_vals, tok, draft, cache, key,
                               finished, done, budget, out_buf, tok_buf,
                               tok_len, proposed, accepted, cfg,
                               self.spec)
        self._verify_tracker.observe(self._verify_jit, ckey[1:], pre)
        return out

    # --------------------------------------------------------------- aot
    def aot_compile(self, batch: int, prompt_len: int, cache_len: int,
                    max_new: int, cfg):
        """AOT-compile the (draft, verify) pair for one fixed padded
        shape — the Predictor's serving mode, persisted through the
        executable store under the new ``generation.spec_draft`` /
        ``generation.spec_verify`` program kinds. Draft mode also
        AOT-compiles the draft model's own prefill bucket so admission
        never traces under traffic."""
        from ..jit import compile_cache
        sess = self.session
        store = sess.executable_store
        spec, k = self.spec, self.spec.k
        sds = jax.ShapeDtypeStruct
        state = tuple(sds(tuple(v.shape), v.dtype)
                      for v in sess.state_values())
        tok = sds((batch,), jnp.int32)
        draft_a = sds((batch, k), jnp.int32)
        key = sds((2,), jnp.uint32)
        flags = sds((batch,), jnp.bool_)
        lane = sds((batch,), jnp.int32)
        out_buf = sds((batch, int(max_new)), jnp.int32)
        tok_buf = sds((batch, int(cache_len)), jnp.int32)
        scalar = sds((), jnp.int32)
        base_sig = compile_cache.network_signature(sess.network)

        def sig_for(kind):
            if base_sig is None:
                return None
            sig = dict(base_sig)
            sig.update(program=(kind, batch, prompt_len, cache_len,
                                max_new),
                       generation=repr(cfg), speculative=repr(spec),
                       operands=compile_cache.aval_signature(state))
            return sig

        # the cache aval comes from the base prefill's abstract trace
        ids = sds((batch, prompt_len), jnp.int32)
        plen = sds((batch,), jnp.int32)
        _, cache_a, _, _ = jax.eval_shape(
            lambda s, i, p, kk: sess._prefill_fn(s, i, p, kk, cfg,
                                                 cache_len),
            state, ids, plen, key)

        if spec.mode == "draft":
            # draft admission path: the draft model's own prefill
            # bucket only (its decode program is never dispatched —
            # the unrolled draft program below replaces it)
            self._draft_session.aot_compile(batch, prompt_len,
                                            cache_len, cfg,
                                            decode=False)
            dstate = tuple(sds(tuple(v.shape), v.dtype)
                           for v in self._draft_session.state_values())
            _, dcache_a, _, _ = jax.eval_shape(
                lambda s, i, p, kk: self._draft_session._prefill_fn(
                    s, i, p, kk, cfg, cache_len),
                dstate, ids, plen, key)
            dexe = compile_cache.build_or_load(
                sig_for("generation.spec_draft"),
                lambda: self._draft_jit.lower(dstate, tok, dcache_a,
                                              lane, spec),
                store=store,
                extra=dict(kind="generation.spec_draft",
                           donation=self._draft_donate),
                label=f"generation.spec_draft.b{batch}k{k}")
            self._compiled[("draft", tok.shape)] = dexe
        else:
            dexe = compile_cache.build_or_load(
                sig_for("generation.spec_draft"),
                lambda: self._draft_jit.lower(tok_buf, lane, spec),
                store=store,
                extra=dict(kind="generation.spec_draft", donation=()),
                label=f"generation.spec_draft.b{batch}k{k}")
            self._compiled[("draft", tok_buf.shape)] = dexe

        vexe = compile_cache.build_or_load(
            sig_for("generation.spec_verify"),
            lambda: self._verify_jit.lower(
                state, tok, draft_a, cache_a, key, flags, lane, lane,
                out_buf, tok_buf, lane, scalar, scalar, cfg, spec),
            store=store,
            extra=dict(kind="generation.spec_verify",
                       donation=self._verify_donate),
            label=f"generation.spec_verify.b{batch}w{k + 1}")
        self._compiled[("verify", tok.shape, out_buf.shape, cache_len,
                        cfg)] = vexe
        return dexe, vexe

    # ------------------------------------------------------------- audit
    def audit(self, batch: int, prompt_len: int, cache_len: int,
              max_new: int, cfg, **audit_kw):
        """Static audit of the (draft, verify) pair for one padded
        shape (nothing executes). Verify is audited with the TPU
        donation INTENT — the KV cache, token buffers, and every lane
        donated — even on CPU; the tier-1 gate asserts zero ERROR
        findings on both and full donation coverage on verify."""
        from ..analysis import audit as _audit
        self.session._ensure_eval()
        base = audit_kw.pop("name", "generation.spec")
        verify_donate = audit_kw.pop(
            "donate", (1, 3, 4, 5, 6, 8, 9, 10, 11, 12))
        draft_donate = audit_kw.pop("draft_donate", (2,))
        spec, k = self.spec, self.spec.k
        sds = jax.ShapeDtypeStruct
        state = tuple(sds(tuple(v.shape), v.dtype)
                      for v in self.session.state_values())
        tok = sds((batch,), jnp.int32)
        draft_a = sds((batch, k), jnp.int32)
        key = sds((2,), jnp.uint32)
        flags = sds((batch,), jnp.bool_)
        lane = sds((batch,), jnp.int32)
        out_buf = sds((batch, int(max_new)), jnp.int32)
        tok_buf = sds((batch, int(cache_len)), jnp.int32)
        scalar = sds((), jnp.int32)
        ids = sds((batch, prompt_len), jnp.int32)
        _, cache_a, _, _ = jax.eval_shape(
            lambda s, i, p, kk: self.session._prefill_fn(
                s, i, p, kk, cfg, cache_len),
            state, ids, lane, key)
        if spec.mode == "draft":
            dstate = tuple(sds(tuple(v.shape), v.dtype)
                           for v in self._draft_session.state_values())
            _, dcache_a, _, _ = jax.eval_shape(
                lambda s, i, p, kk: self._draft_session._prefill_fn(
                    s, i, p, kk, cfg, cache_len),
                dstate, ids, lane, key)
            draft_report = _audit(
                self._draft_fn, dstate, tok, dcache_a, lane, spec,
                static_argnums=(4,), donate=draft_donate,
                name=f"{base}.draft", **audit_kw)
        else:
            draft_report = _audit(
                self._draft_fn, tok_buf, lane, spec,
                static_argnums=(2,), name=f"{base}.draft",
                **audit_kw)
        verify_report = _audit(
            self._verify_fn, state, tok, draft_a, cache_a, key, flags,
            lane, lane, out_buf, tok_buf, lane, scalar, scalar, cfg,
            spec, static_argnums=(13, 14), donate=verify_donate,
            name=f"{base}.verify", **audit_kw)
        return draft_report, verify_report


# ----------------------------------------------------------- host loop

def decode_loop(network, session, state_vals, ids, plen, cfg, spec,
                draft_model, cache_len, max_new_tokens, key, live_rows,
                poll_every: int = 4):
    """The speculative ``generate()`` host loop: one base prefill, then
    draft+verify window dispatches until every row finishes (eos or
    budget). Rows advance RAGGEDLY — per-row emit counts live on
    device; the host polls one tiny bool every ``poll_every`` windows
    (never per window). Returns the [B, max_new_tokens] int32 result
    with post-eos padding, identical in contract (and, under greedy,
    bitwise) to the sequential path."""
    spec_sess = session.speculative(spec, draft_model)
    b = ids.shape[0]
    tok, cache, key, finished = session.prefill(
        state_vals, jnp.asarray(ids), jnp.asarray(plen), key, cfg,
        cache_len)
    if monitor.enabled:
        monitor.record_generation(prefill_steps=1)

    dstate = dcache = None
    if spec.mode == "draft":
        dsess = spec_sess._draft_session
        dstate = dsess.state_values()
        _, dcache, _, _ = dsess.prefill(
            dstate, jnp.asarray(ids), jnp.asarray(plen), key, cfg,
            cache_len)
        if monitor.enabled:
            monitor.record_generation(prefill_steps=1)

    pad = jnp.int32(cfg.pad_value)
    # decode into the compiled out-buffer width when one is registered
    # (the Predictor's smaller-than-budget max_new_tokens path): budget
    # is a lane, so rows still stop at max_new_tokens and the result is
    # sliced back — but every dispatch stays on a warm executable
    width = spec_sess.registered_buf_width(b, cache_len, cfg,
                                           max_new_tokens)
    out_buf = jnp.full((b, width), pad, jnp.int32).at[:, 0].set(tok)
    # token history for the drafter: padded prompt + the pending token
    hist = np.full((b, cache_len), int(cfg.pad_value), np.int32)
    hist[:, :ids.shape[1]] = ids
    tok_buf = jnp.asarray(hist).at[jnp.arange(b), jnp.asarray(plen)] \
        .set(tok)
    tok_len = jnp.asarray(plen, jnp.int32) + 1
    done = jnp.ones((b,), jnp.int32)
    budget = jnp.full((b,), max_new_tokens, jnp.int32)
    finished = finished | (done >= budget)
    # two buffers, not one bound twice: both lanes are donated to the
    # verify step, and one buffer cannot be donated twice
    proposed = jnp.zeros((), jnp.int32)
    accepted = jnp.zeros((), jnp.int32)

    for w in range(max_new_tokens - 1):
        if spec.mode == "draft":
            draft, dcache = spec_sess.draft(dstate, tok, dcache,
                                            cache.kv_len)
        else:
            draft = spec_sess.draft(tok_buf, tok_len)
        (tok, cache, key, finished, done, out_buf, tok_buf, tok_len,
         proposed, accepted) = spec_sess.verify(
            state_vals, tok, draft, cache, key, finished, done, budget,
            out_buf, tok_buf, tok_len, proposed, accepted, cfg)
        if monitor.enabled:
            monitor.record_generation(decode_steps=1)
        # ragged progress: one tiny bool read every poll_every windows
        # (never per window — that would drain the dispatch queue);
        # every live row emits >= 1 token per window, so the loop also
        # terminates unpolled after max_new_tokens - 1 windows
        if (w + 1) % poll_every == 0 and \
                bool(jnp.all(finished)):  # lint: host-sync-ok (every-K-window poll)
            break

    result = out_buf[:, :max_new_tokens] if width > max_new_tokens \
        else out_buf
    if monitor.enabled:
        live = b if live_rows is None else min(int(live_rows), b)
        np_prop = int(proposed)  # lint: host-sync-ok (end-of-call counter read)
        np_acc = int(accepted)  # lint: host-sync-ok (end-of-call counter read)
        monitor.record_speculative(np_prop, np_acc)
        arr = np.asarray(result[:live])  # lint: host-sync-ok (one end-of-call read)
        done_h = np.asarray(done)  # lint: host-sync-ok (same end-of-call read)
        if cfg.eos_token_id is not None:
            hit = arr == cfg.eos_token_id
            per_row = np.where(hit.any(1), hit.argmax(1) + 1,
                               max_new_tokens)
            tokens = int(per_row.sum())
        else:
            tokens = int(done_h[:live].sum())
        monitor.record_generation(tokens=tokens)
        # occupancy from tokens ACTUALLY emitted (same contract as the
        # sequential path's n_done) — an early-eos batch must not read
        # as a full ring
        plen_h = np.asarray(plen)  # lint: host-sync-ok (host-side plen)
        monitor.record_cache_occupancy(
            int(np.max(plen_h + done_h)) / cache_len)
    return Tensor(result)

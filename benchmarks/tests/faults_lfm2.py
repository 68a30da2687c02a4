"""The faults only a hybrid of convolution and attention layers with a
biased sigmoid router can have, planted underneath an otherwise whole
run of an ``lfm2`` cell (``run.main(argv, patch=...)``):

* ``state_at_bucket_end``: a prefill runs at a padded bucket and hands on
  the convolution state at the bucket's end (the padding's) instead of
  the one at ``prompt_len``;
* ``stale_state``: an admission installs the KV row and leaves the slot's
  convolution state as its last holder left it;
* ``bias_in_weights``: the router's per-expert bias is added to the
  experts' weights as well as to the selection;
* ``qk_norm_after_rotary``: the q/k head norms are applied after rotary.

Each has to come out as not correct by the cell's own limits.  By hand,
on the chip as on the CPU:

    python3 benchmarks/tests/faults_lfm2.py stale_state \\
        --workload lfm2-l14-offline --seed 7 --seconds 20
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "drivers")):
    if p not in sys.path:
        sys.path.insert(0, p)

_UNDO = []      # (object, attribute, sound value) of every planted fault


def _plant(obj, name, value):
    _UNDO.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def restore():
    """Put the program back (the tests plant one fault after another)."""
    while _UNDO:
        obj, name, value = _UNDO.pop()
        setattr(obj, name, value)


def _fresh_programs(fam):
    """A planted fault changes what a program computes, not what the
    executable store keys it by: build every program anew."""
    from paddle_tpu.jit import compile_cache
    compile_cache.set_default_store(None)   # an earlier run's, in tests
    fam.enable_compile_cache = lambda path: None


def state_at_bucket_end(fam):
    import jax.numpy as jnp
    from paddle_tpu.generation import hybrid_cache
    _fresh_programs(fam)
    sound = hybrid_cache.window_state

    def at_the_end(prior, window, valid):
        return sound(prior, window,
                     jnp.full_like(valid, window.shape[1]))

    _plant(hybrid_cache, "window_state", at_the_end)


def stale_state(fam):
    from paddle_tpu.generation.hybrid_cache import HybridCache
    _fresh_programs(fam)

    def install_kv_only(self, src, slot, *where):
        return HybridCache(self.kv.install_row(src.kv, slot, *where),
                           self.state)

    _plant(HybridCache, "install_row", install_kv_only)


def bias_in_weights(fam):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.parallel import moe
    _fresh_programs(fam)
    sound = moe.dropless_moe

    def biased_weights(x, router_w, w_gate_up, w_down, top_k,
                       norm_topk_prob=True, router="softmax",
                       select_bias=None, scaling=1.0):
        if select_bias is None:
            return sound(x, router_w, w_gate_up, w_down, top_k,
                         norm_topk_prob, router, None, scaling)
        # the score the layer weighs by IS the biased one
        sigmoid = jax.nn.sigmoid
        jax.nn.sigmoid = lambda z: sigmoid(z) \
            + select_bias.astype(jnp.float32)
        try:
            return sound(x, router_w, w_gate_up, w_down, top_k,
                         norm_topk_prob, router, None, scaling)
        finally:
            jax.nn.sigmoid = sigmoid

    _plant(moe, "dropless_moe", biased_weights)


def qk_norm_after_rotary(fam):
    from paddle_tpu.core.tensor import dispatch
    from paddle_tpu.models import decoder
    _fresh_programs(fam)

    def rotary_then_norm(self, q, k, pos):
        def impl(q_, k_, pos_, gq, gk):
            q_ = decoder.rotary(q_, pos_, self.theta)
            k_ = decoder.rotary(k_, pos_, self.theta)
            return decoder._head_rms(q_, gq, self.eps), \
                decoder._head_rms(k_, gk, self.eps)
        return dispatch("qk_norm_rotary", impl,
                        (q, k, pos, self.q_norm, self.k_norm), {})

    _plant(decoder.RotaryGQAttention, "_qk", rotary_then_norm)


FAULTS = {"state_at_bucket_end": state_at_bucket_end,
          "stale_state": stale_state, "bias_in_weights": bias_in_weights,
          "qk_norm_after_rotary": qk_norm_after_rotary}

if __name__ == "__main__":
    import run as run_mod
    fault = FAULTS[sys.argv[1]]
    rc = run_mod.main(sys.argv[2:], patch=fault)
    print(f"fault {sys.argv[1]}: correct={run_mod.main.last['correct']}",
          file=sys.stderr)
    sys.exit(rc)
